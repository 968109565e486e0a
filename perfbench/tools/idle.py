"""Which host phase holds each device idle gap: traced runs of a cell in
this process whose trace keeps the engine's own phases (``engine.*``
annotations, ``repro.obs.trace``) beside the harness's ``bench.*`` ones,
all on the device trace's clock.

    python3 perfbench/tools/idle.py c32-frames-max c64-frames-max \
        --seeds 2147480101 --seconds 51 --out bench_out/idle.jsonl

Each run is ``harness.run_cell`` with tracing on, as ``run.py --trace 1``
runs it, its trace read by ``extract`` instead of ``trace.extract``.
``attribute`` then names every idle gap of the traced window by the
innermost host span holding it, totals the ``engine.*`` spans inside the
window (``host_spans``) and sums the device idle time under each
innermost span name (``idle_by_span``), the device's times first moved by
``clock_shift``, the least shift that puts every chunk after the host
call that launched it (the unshifted reading is kept beside it).
``admit_host_us``, ``fetch_wait_us`` and ``poll_host_us`` read the
serving thread's mean time per admission, per stats fetch and per
scheduler round (less the fetch) from ``host_spans``; each returns None
where the program records no such span.  One JSON line per run goes to standard output and to
``--out``; ``--keep DIR`` keeps each run's compact trace.
"""

from __future__ import annotations

import argparse
import bisect
import json
import pathlib
import statistics
import sys
import tempfile
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench import trace, work  # noqa: E402

HOST_PREFIXES = ("bench.", "engine.")
NO_SPAN = "host"  # idle time under no host annotation, as trace.reduce


def extract(path: str) -> dict:
    """``trace.extract``, with the host's ``engine.*`` events kept beside
    ``bench.*``."""
    import jax

    compact = trace.extract(path)
    pd = jax.profiler.ProfileData.from_file(path)
    compact["host"] = sorted(
        ([e.name, int(e.start_ns), int(e.duration_ns)]
         for plane in pd.planes if plane.name.startswith("/host:")
         for line in plane.lines for e in line.events
         if e.name.startswith(HOST_PREFIXES)),
        key=lambda e: e[1])
    return compact


def clock_shift(compact: dict, launches: dict) -> int:
    """Nanoseconds to add to the device's times so that no program
    starts before the host call that launched it began: the profiler
    aligns the host's and the device's clocks only to about a
    millisecond, so this is a lower bound on the error.  ``launches``
    maps a host span to the program it launches; the i-th call launches
    the (i + j)-th execution, for the j whose start lags spread least,
    then lie nearest zero (the capture cuts calls and executions at
    different places)."""
    devs = [d for d in compact["devices"] if d["ops"]]
    if not devs:
        return 0
    shift = 0
    for span, program in launches.items():
        starts = sorted(m[1] for m in devs[0]["modules"]
                        if trace._program(m[0]) == program)
        calls = [h[1] for h in compact["host"] if h[0] == span]
        best = None
        for j in range(-8, 9):
            lags = [starts[i] - calls[i - j] for i in range(len(starts))
                    if 0 <= i - j < len(calls)]
            if len(lags) * 2 < len(starts) or not lags:
                continue
            mid = statistics.median(lags)
            key = (mid - min(lags), abs(mid), min(lags))
            best = key if best is None else min(best, key)
        if best is not None:
            shift = max(shift, -best[2])
    return shift


def _window(compact: dict, ops):
    marks = [e for e in compact["host"] if e[0] == "bench.traced"]
    if marks:
        return marks[0][1], marks[0][1] + marks[0][2]
    return min(o[1] for o in ops), max(o[1] + o[2] for o in ops)


def _gaps(ops, lo, hi):
    """Device idle intervals inside [lo, hi], as ``trace.reduce``."""
    busy = trace._union(trace._clip(
        trace._union((s, s + d) for _, s, d in ops), lo, hi))
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def _nest(events):
    """Host spans as [name, start, end, parent index, children] in start
    order; a span's parent is the innermost earlier span that holds its
    start (annotations of one thread nest)."""
    out, stack = [], []
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            out[parent][4].append(len(out))
        out.append([name, s, s + d, parent, []])
        stack.append(len(out) - 1)
    return out


def _innermost(spans, lo, hi):
    """[(start, end, span index or None)] cutting [lo, hi] by the
    innermost span open at each instant."""
    segs, stack, t = [], [], lo

    def upto(x):
        nonlocal t
        if x > t:
            segs.append((t, x, stack[-1][1] if stack else None))
            t = x

    for i, (_, s, e, _, _) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            upto(stack[-1][0])
            stack.pop()
        upto(s)
        stack.append((e, i))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    upto(hi)
    return segs


def _overlap(span, s, e) -> int:
    return min(e, span[2]) - max(s, span[1])


def _name_gap(spans, roots, root_starts, inner, s, e) -> str:
    """The innermost ``engine.*`` span that holds the whole gap; else,
    from the outermost span holding most of it, the child that holds at
    least half of it, as long as there is one."""
    i = inner
    while i is not None:
        name, hs, he, parent, _ = spans[i]
        if name.startswith("engine.") and hs <= s and he >= e:
            return name
        i = parent
    best, at = 0, None
    k = bisect.bisect_left(root_starts, e) - 1
    while k >= 0 and spans[roots[k]][2] > s:
        ov = _overlap(spans[roots[k]], s, e)
        if ov >= best and ov > 0:  # ties go to the earlier span
            best, at = ov, roots[k]
        k -= 1
    if at is None:
        return NO_SPAN
    while True:
        kids = [(_overlap(spans[c], s, e), c) for c in spans[at][4]]
        ov, c = max(kids, default=(0, None), key=lambda kc: kc[0])
        if c is None or 2 * ov < e - s:
            return spans[at][0]
        at = c


def attribute(compact: dict, top: int = 10, shift: int = 0) -> dict:
    """Idle gaps named by host span, ``host_spans`` and
    ``idle_by_span`` of a compact trace (``extract``'s form), over the
    window ``trace.reduce`` uses and the first device's busy time, the
    device's times moved by ``shift`` ns (``clock_shift``)."""
    devs = [d for d in compact["devices"] if d["ops"]]
    if not devs:
        return {"idle_gaps": [], "host_spans": {}, "idle_by_span": {}}
    ops = [(n, s + shift, d) for n, s, d in devs[0]["ops"]]
    lo, hi = _window(compact, ops)
    spans = _nest(e for e in compact["host"] if e[0] != "bench.traced")
    host_spans: dict = {}
    for name, s, e, _, _ in spans:
        if not name.startswith("engine.") or e <= lo or s >= hi:
            continue
        h = host_spans.setdefault(name, {"time_s": 0.0, "count": 0.0})
        h["time_s"] += (min(e, hi) - max(s, lo)) * 1e-9
        h["count"] += trace._share_inside(s, e - s, lo, hi)
    segs = _innermost(spans, lo, hi)
    seg_starts = [g[0] for g in segs]
    roots = [i for i, sp in enumerate(spans) if sp[3] is None]
    root_starts = [spans[i][1] for i in roots]
    idle_by_span: dict = {}
    named = []
    for s, e in _gaps(ops, lo, hi):
        k = bisect.bisect_right(seg_starts, s) - 1
        mid = bisect.bisect_right(seg_starts, (s + e) // 2) - 1
        while k < len(segs) and segs[k][0] < e:
            a, b, i = segs[k]
            name = NO_SPAN if i is None else spans[i][0]
            idle_by_span[name] = idle_by_span.get(name, 0.0) + (
                min(b, e) - max(a, s)) * 1e-9
            k += 1
        named.append((s, e, _name_gap(spans, roots, root_starts,
                                      segs[mid][2], s, e)))
    named.sort(key=lambda g: g[0] - g[1])
    return {
        "idle_gaps": [[n, (e - s) * 1e-9] for s, e, n in named[:top]],
        "host_spans": host_spans,
        "idle_by_span": dict(sorted(idle_by_span.items(),
                                    key=lambda kv: -kv[1])),
    }


def span_mean_us(summary, name: str):
    """Mean length of the ``name`` spans inside the window, or None."""
    h = ((summary or {}).get("host_spans") or {}).get(name)
    if not h or not h["count"]:
        return None
    return h["time_s"] / h["count"] * 1e6


def admit_host_us(summary):
    """The serving thread's time to upload one train and enqueue its
    encode: the mean ``engine.admit`` span."""
    return span_mean_us(summary, "engine.admit")


def fetch_wait_us(summary):
    """The host blocked on a chunk's stats: the mean
    ``engine.retire.fetch`` span."""
    return span_mean_us(summary, "engine.retire.fetch")


def poll_host_us(summary):
    """The serving thread's own work per scheduler round: the
    ``engine.poll`` spans less the stats fetches (which the engine makes
    only inside a poll), per poll."""
    spans = (summary or {}).get("host_spans") or {}
    poll = spans.get("engine.poll")
    if not poll or not poll["count"]:
        return None
    fetch = spans.get("engine.retire.fetch", {"time_s": 0.0})["time_s"]
    return (poll["time_s"] - fetch) / poll["count"] * 1e6


def _run(cell, seed: int, seconds: float, devices, keep_dir=None) -> dict:
    from perfbench import harness, spec

    programs = spec.named("systems", cell.config["system"]).PROGRAMS
    with tempfile.TemporaryDirectory(prefix="perfbench_idle_") as tmp:
        keep = pathlib.Path(keep_dir or tmp) / f"{cell.name}_{seed}.json"
        out = harness.run_cell(cell, seed, seconds, True, time.perf_counter(),
                               devices=devices, keep_trace=keep,
                               log=lambda *a: print(*a, file=sys.stderr))
        compact = json.loads(keep.read_text())
    # the chunk's launches: the admissions queue behind each other, so
    # their start lags do not single out one alignment
    shift = clock_shift(compact, {"engine.tick.dispatch": programs["chunk"]})
    raw = attribute(compact)
    summary = attribute(compact, shift=shift)
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    cfg = cell.config
    spans = summary["host_spans"]
    polls = spans.get("engine.poll", {}).get("count") or float("nan")
    prep_dispatch = sum(spans.get(f"engine.tick.{k}", {}).get("time_s", 0.0)
                        for k in ("prep", "dispatch"))
    idle_s = sum(summary["idle_by_span"].values())
    return {
        "cell": cell.name,
        "seed": seed,
        "correct": out["correct"],
        # step.mfu.tput is windows_per_s times a constant
        "windows_per_s": metrics["step.mfu.tput"] / 100.0
        * work.peaks(out["device"]["kind"])["flops_per_s"]
        / work.flops_per_window(cfg),
        "metrics": metrics,
        "busy_s": out["device"].get("busy_s"),
        "window_s": out["device"].get("window_s"),
        "idle_s": idle_s,
        "admit.host_us": admit_host_us(summary),
        "tick.fetch_wait_us": fetch_wait_us(summary),
        "poll.host_us": poll_host_us(summary),
        "prep_dispatch_per_poll_us": prep_dispatch / polls * 1e6,
        "device_clock_shift_us": shift * 1e-3,
        "idle_by_span": summary["idle_by_span"],
        "idle_gaps": summary["idle_gaps"],
        "idle_by_span_unshifted": raw["idle_by_span"],
        "host_spans": spans,
    }


def main(argv=None):
    from perfbench import harness, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--seeds", default="5")
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep", default=None,
                    help="directory to keep each run's compact trace in")
    args = ap.parse_args(argv)
    harness.enable_compile_cache(ROOT)
    # the harness's traced run, with a trace that keeps engine.* events
    harness.trace_mod = types.SimpleNamespace(extract=extract,
                                              reduce=trace.reduce)
    out = keep = None
    if args.out:
        out = ROOT / args.out
        out.parent.mkdir(parents=True, exist_ok=True)
    if args.keep:
        keep = ROOT / args.keep
        keep.mkdir(parents=True, exist_ok=True)
    for name in args.cells:
        cell = spec.cell(spec.load(ROOT), ROOT, name)
        devices = harness.check_chips(cell.chips)
        for seed in (int(s) for s in args.seeds.split(",")):
            line = json.dumps(_run(cell, seed, args.seconds, devices, keep))
            print(line, flush=True)
            if out is not None:
                with out.open("a") as f:
                    f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
