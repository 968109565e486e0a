"""From a profiler trace to device metrics.

``extract`` reads an ``.xplane.pb`` (``jax.profiler.ProfileData``) into a
compact form: per device, its operations (line "XLA Ops") and its program
executions (line "XLA Modules"), and the host's ``bench.*`` annotations,
all as ``[name, start_ns, duration_ns]`` on the trace's one clock (an
operation by its HLO name, ``fusion.20``).
``reduce`` turns that form into numbers:

- busy time: the union of the device's operation intervals, so that an
  operation nested in another (a fusion inside a ``while`` body, which the
  trace lists on the same line) is counted once;
- per program (module name without its ``(id)``): the time of its
  top-level operations, those no other operation of the device encloses,
  and its executions;
- per kernel name: the union of the intervals of the operations so named,
  and its calls;
- times are clipped to the traced window, and an execution the window's
  edge cuts counts as the share of it inside;
- ``device_ops``: the top-level operations that took most time, as
  ``program/op``; ``idle_gaps``: the longest gaps between busy intervals,
  named by the host annotation that overlaps each most.
"""

from __future__ import annotations

import re

_MODULE_ID = re.compile(r"\(\d+\)$")


def extract(path: str) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [
                        [op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)]
                        for e in line.events
                    ]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name.startswith("bench.")
                )
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def op_name(text: str) -> str:
    """``fusion.20`` of an op event's name, which on a TPU is the whole
    HLO instruction (``%fusion.20 = s32[...] fusion(...), ...``)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _union(intervals):
    """Merged, sorted (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _share_inside(start, dur, lo, hi) -> float:
    """The share of an execution that lies inside the window, so that
    one cut by the window's edge counts as part of a call."""
    if dur <= 0:
        return float(lo <= start < hi)
    return max(0.0, min(start + dur, hi) - max(start, lo)) / dur


def _top_level(ops):
    """Operations that no earlier-starting operation encloses."""
    top, end = [], None
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        if end is not None and s + d <= end:
            continue
        top.append((name, s, d))
        end = s + d if end is None else max(end, s + d)
    return top


def _program(name: str) -> str:
    return _MODULE_ID.sub("", name)


def reduce(compact: dict, kernels=(), window=None) -> dict:
    """Numbers of a compact trace.  ``window`` (start_ns, end_ns) bounds
    the traced interval; without it the host's ``bench.traced``
    annotation does, else the span of the device's operations."""
    if window is None:
        marks = [e for e in compact["host"] if e[0] == "bench.traced"]
        if marks:
            window = (marks[0][1], marks[0][1] + marks[0][2])
    per_dev = []
    for dev in compact["devices"]:
        ops = dev["ops"]
        if not ops:
            continue
        lo, hi = window if window else (
            min(o[1] for o in ops), max(o[1] + o[2] for o in ops))
        ops = [o for o in ops if o[1] + o[2] > lo and o[1] < hi]
        busy = _union((s, s + d) for _, s, d in ops)
        busy = _union(_clip(busy, lo, hi))
        mods = sorted(
            (m for m in dev["modules"] if m[1] + m[2] > lo and m[1] < hi),
            key=lambda m: m[1])
        top = _top_level(ops)
        programs: dict = {}
        op_time: dict = {}
        mi = 0
        for name, s, d in top:
            while mi + 1 < len(mods) and mods[mi + 1][1] <= s:
                mi += 1
            prog = "?"
            if mods and mods[mi][1] <= s < mods[mi][1] + mods[mi][2]:
                prog = _program(mods[mi][0])
            d = min(s + d, hi) - max(s, lo)  # the part inside the window
            p = programs.setdefault(prog, {"time_s": 0.0, "calls": 0})
            p["time_s"] += d * 1e-9
            key = f"{prog}/{name}"
            op_time[key] = op_time.get(key, 0.0) + d * 1e-9
        for m in mods:
            programs.setdefault(
                _program(m[0]), {"time_s": 0.0, "calls": 0}
            )["calls"] += _share_inside(m[1], m[2], lo, hi)
        kern = {}
        for k in kernels:
            hits = _union((s, s + d) for n, s, d in ops
                          if n == k or n.startswith(k + "."))
            kern[k] = {
                "time_s": sum(e - s for s, e in _clip(hits, lo, hi)) * 1e-9,
                "calls": sum(_share_inside(s, e - s, lo, hi)
                             for s, e in hits),
            }
        gaps = []
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        per_dev.append({
            "window_s": (hi - lo) * 1e-9,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "programs": programs,
            "kernels": kern,
            "op_time": op_time,
            "gaps": gaps,
        })
    if not per_dev:
        return {"window_s": None, "busy_s": 0.0, "programs": {},
                "kernels": {}, "device_ops": [], "idle_gaps": []}
    first = per_dev[0]
    n = len(per_dev)
    ops_total: dict = {}
    for d in per_dev:
        for k, v in d["op_time"].items():
            ops_total[k] = ops_total.get(k, 0.0) + v / n
    gaps = sorted(first["gaps"], key=lambda g: g[0] - g[1])[:10]
    annotations = [e for e in compact["host"] if e[0] != "bench.traced"]
    return {
        "window_s": first["window_s"],
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "programs": first["programs"],
        "kernels": first["kernels"],
        "device_ops": sorted(
            ([k, v] for k, v in ops_total.items()),
            key=lambda kv: -kv[1])[:10],
        "idle_gaps": [
            [_host_doing(annotations, s, e), (e - s) * 1e-9]
            for s, e in gaps
        ],
    }


def _host_doing(annotations, s, e) -> str:
    best, name = 0, "host"
    for n, hs, hd in annotations:
        if hs >= e:
            break
        ov = min(e, hs + hd) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name
