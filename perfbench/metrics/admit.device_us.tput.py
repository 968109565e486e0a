"""Device time of the admission program per admission, traced (backlog)."""

from perfbench.metrics import _lib


def read(rec):
    return _lib.program_us_per_call(rec, "admit")
