"""Device time of the fused chunk kernel per layer-0 event it served,
traced: its time per call over the events of a call.  A call's events are
its active slot-steps, counted as ``snn_chunk_roofline`` counts them,
times the mean layer-0 events a step of the requests served.  It reads
how the weight-row gathers cost as the slab and the events a step grow;
silent where no kernel of that name ran."""

import numpy as np

from perfbench.metrics import _lib

MAY_BE_ABSENT = True


def read(rec):
    us = _lib.kernel_us(rec)
    admit = _lib._program(rec, "admit")
    if us is None or not admit or not admit["calls"]:
        return None
    cfg, s = rec.cell.config, rec.system
    slot_steps = min(admit["calls"] * cfg["num_steps"]
                     / _lib.kernel(rec)["calls"],
                     s["num_slots"] * s["chunk_steps"])
    per_step = [float(r.result.events_per_layer[0]) / cfg["num_steps"]
                for r in rec.requests
                if r.result is not None and r.result.ok]
    if not per_step or np.mean(per_step) <= 0:
        return None
    return us * 1e3 / (slot_steps * float(np.mean(per_step)))
