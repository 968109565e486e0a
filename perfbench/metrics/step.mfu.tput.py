"""Dense-equivalent FLOPs of the windows completed per second over the
chip's peak."""

from perfbench.metrics import _lib


def read(rec):
    return _lib.step_mfu_pct(rec)
