"""Device time of the fused chunk kernel per call, traced; silent where
no kernel of that name ran, as when a later program takes it off the path
(``step.mfu.tput`` still bounds the step)."""

from perfbench.metrics import _lib

MAY_BE_ABSENT = True


def read(rec):
    return _lib.kernel_us(rec)
