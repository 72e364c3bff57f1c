"""Pallas TPU kernels for the perf-critical operators (paper Fig. 3):
embedding-bag gather-pool, DLRM dot interaction, xDeepFM CIN, flash-decode
attention.  ``ops`` holds the jit'd public wrappers; ``ref`` the oracles.
DIEN's recurrence kernel (``gru``) is called by ``layers.rnn``, whose scan
is its oracle."""
from repro.kernels import ops, ref  # noqa: F401
