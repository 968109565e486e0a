"""Share of the traced window with no operation on the device (backlog)."""

from perfbench.metrics import _lib


def read(rec):
    return _lib.idle_share_pct(rec)
