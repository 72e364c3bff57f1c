"""The longest garbage collection that started inside the window, from the
program's pause counter (every collection of the process, by its
``gc.callbacks`` hook); 0 where none started."""
import program


def read(run):
    rows = program.pauses(run.window)
    if rows is None:
        return None
    d = rows["end"] - rows["start"]
    return float(d.max() * 1e3) if len(d) else 0.0
