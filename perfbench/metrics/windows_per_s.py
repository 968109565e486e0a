"""Windows completed inside the window, per second of the window."""

from perfbench.metrics import _lib


def read(rec):
    return _lib.windows_per_s(rec)
