"""Operations and bytes of the served work, from shapes alone, and the
chip's peaks (``peaks.json``, keyed by ``device_kind``).

Both count the same work whatever implements it, so that no kernel can
read above its roofline:

- dense-equivalent FLOPs: 2 * fan_in * fan_out per layer and time step of
  an active slot, as a dense matmul of the step's input would take;
- least bytes of a chunk call: the call's ring slices (event addresses
  and values, Tc x C per active slot, and the per-step counts) and the
  neuron states it reads and writes.  Weights are left out: whether they
  are staged whole or gathered by row is the kernel's choice, and leaving
  them out only lowers the bound.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS.name}"
        )
    return table[device_kind]


def flops_per_step(layer_sizes) -> float:
    return float(sum(2 * a * b for a, b in zip(layer_sizes[:-1],
                                                layer_sizes[1:])))


def flops_per_window(cfg: dict) -> float:
    return cfg["num_steps"] * flops_per_step(cfg["layer_sizes"])


def chunk_bytes(cfg: dict, slot_steps: float, active_slots: float,
                capacity: int, addr_bytes: int) -> float:
    """Least bytes of one chunk call with ``slot_steps`` active slot-steps
    over ``active_slots`` active slots: int16 (``addr_bytes``) addresses
    and int8 values of C events per slot-step, an int32 count per
    slot-step, and every neuron's float32 membrane and int32 refractory
    count read and written once per active slot."""
    ring = slot_steps * (capacity * (addr_bytes + 1) + 4)
    neurons = sum(cfg["layer_sizes"][1:])
    states = active_slots * neurons * (4 + 4) * 2
    return float(ring + states)


def least_time(flops: float, nbytes: float, peak: dict):
    """(seconds, bound): the larger of FLOPs over peak FLOP/s and bytes
    over peak bytes/s, and which of the two it is."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
