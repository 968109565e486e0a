"""The fused chunk kernel's least time over its traced time per call;
silent where no kernel of that name ran, as when a later program takes it off the path
(``step.mfu.tput`` still bounds the step)."""

from perfbench.metrics import _lib

MAY_BE_ABSENT = True


def read(rec):
    return _lib.kernel_roofline_pct(rec)
