"""Arithmetic the metric readers share."""

from __future__ import annotations

from perfbench import work


def windows_per_s(rec) -> float:
    return rec.completed_in_window / rec.seconds


def tick_host_us(rec):
    if not rec.tick.get("ticks"):
        return None
    return rec.tick["host_prep_us"] + rec.tick["dispatch_us"]


def _program(rec, role: str):
    if rec.trace is None:
        return None
    return rec.trace["programs"].get(rec.system["programs"][role])


def program_us_per_call(rec, role: str):
    p = _program(rec, role)
    if not p or not p["calls"]:
        return None
    return p["time_s"] / p["calls"] * 1e6


def kernel(rec):
    if rec.trace is None:
        return None
    k = rec.trace["kernels"].get(rec.system["programs"]["kernel"])
    return k if k and k["calls"] else None


def kernel_us(rec):
    k = kernel(rec)
    return None if k is None else k["time_s"] / k["calls"] * 1e6


def kernel_roofline_pct(rec):
    """The kernel's least time over its measured time.  Active
    slot-steps per call are the traced admissions times the window's
    steps over the traced chunk calls, at most slots x chunk steps."""
    k = kernel(rec)
    admit = _program(rec, "admit")
    if k is None or not admit or not admit["calls"]:
        return None
    cfg, s = rec.cell.config, rec.system
    per_call = min(admit["calls"] * cfg["num_steps"] / k["calls"],
                   s["num_slots"] * s["chunk_steps"])
    flops = per_call * work.flops_per_step(cfg["layer_sizes"])
    nbytes = work.chunk_bytes(cfg, per_call, per_call / s["chunk_steps"],
                              s["capacity"], s["addr_bytes"])
    least, _ = work.least_time(flops, nbytes, work.peaks(rec.device_kind))
    return least / (k["time_s"] / k["calls"]) * 100.0


def step_mfu_pct(rec) -> float:
    peak = work.peaks(rec.device_kind)["flops_per_s"]
    return (windows_per_s(rec) * work.flops_per_window(rec.cell.config)
            / peak * 100.0)


def idle_share_pct(rec):
    t = rec.trace
    if t is None or not t["window_s"] or t["busy_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
