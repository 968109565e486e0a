"""Host time per tick: scheduling prep plus chunk dispatch (backlog)."""

from perfbench.metrics import _lib


def read(rec):
    return _lib.tick_host_us(rec)
