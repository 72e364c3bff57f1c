"""Seconds from the benchmark's start to the window's first scheduled
arrival: weights made on the device, the payload pool drawn, the cell's
buckets warmed."""


def read(run):
    return run.setup_s
