"""Where the host stops: runs of a cell in this process, with a thread
that samples the main thread's Python stack and its scheduler counters
every few milliseconds.  Every call of the system's ``poll`` or
``submit`` that lasts longer than ``--stall-ms``, and every such gap of
the harness's loop between them outside a sleep, is listed with what the
samples inside it show: the innermost frame, the innermost frame of the
program or the harness, the CPU time of the main thread and of the whole
process across it, the main thread's time waiting for a CPU
(``/proc/self/task/<tid>/schedstat``, where the kernel keeps it), and
how late the sampler itself woke (it cannot run while the main thread
holds the GIL).

    python3 perfbench/tools/stalls.py c64-frames-max --rate 37.2 \
        --seeds 11,12 --seconds 51 --out bench_out/stalls.jsonl

``--rate`` turns the cell's mix into open-loop Poisson arrivals at that
rate; without it the cell's own mix runs.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench import harness, spec  # noqa: E402

PROGRAM_DIRS = ("/src/repro/", "/perfbench/")


def _where(code) -> str:
    return f"{code.co_filename.rsplit('/', 2)[-1]}:{code.co_name}"


def _stack(frame):
    """(innermost frame, innermost frame of the program or harness)."""
    inner = own = None
    while frame is not None:
        here = f"{_where(frame.f_code)}:{frame.f_lineno}"
        inner = inner or here
        if own is None and any(d in frame.f_code.co_filename
                               for d in PROGRAM_DIRS):
            own = here
        frame = frame.f_back
    return inner, own


def _schedstat(tid: int):
    """Nanoseconds the thread ran and waited for a CPU; None where the
    kernel keeps no scheduler statistics."""
    try:
        with open(f"/proc/self/task/{tid}/schedstat") as f:
            run_ns, wait_ns, _ = f.read().split()
        return int(run_ns), int(wait_ns)
    except (OSError, ValueError):
        return None, None


class Sampler(threading.Thread):
    def __init__(self, period_s: float):
        super().__init__(daemon=True)
        self.period_s = period_s
        self.main = threading.main_thread()
        self.main_clock = time.pthread_getcpuclockid(self.main.ident)
        # (t, main thread CPU s, process CPU s, runqueue ns, inner, own)
        self.samples = []
        self._halt = threading.Event()

    def run(self):
        frames = sys._current_frames
        while not self._halt.wait(self.period_s):
            t = time.perf_counter()
            cpu = time.clock_gettime(self.main_clock)
            proc = time.process_time()
            _, wait_ns = _schedstat(self.main.native_id)
            inner, own = _stack(frames().get(self.main.ident))
            self.samples.append((t, cpu, proc, wait_ns, inner, own))

    def stop(self):
        self._halt.set()
        self.join()


def _summary(kind, t0, t1, samples, window_t0):
    inside = [s for s in samples if t0 <= s[0] <= t1]
    before = [s for s in samples if s[0] < t0]
    after = [s for s in samples if s[0] > t1]
    edge0 = before[-1] if before else None
    edge1 = after[0] if after else None
    times = ([edge0[0]] if edge0 else []) + [s[0] for s in inside] + (
        [edge1[0]] if edge1 else [])
    row = {"kind": kind, "at_s": round(t0 - window_t0, 4),
           "ms": round((t1 - t0) * 1e3, 3), "samples": len(inside),
           "sampler_gap_ms": round(max(
               (b - a for a, b in zip(times, times[1:])), default=0) * 1e3,
               3)}
    if edge0 and edge1:
        row["bracket_ms"] = round((edge1[0] - edge0[0]) * 1e3, 3)
        row["main_cpu_ms"] = round((edge1[1] - edge0[1]) * 1e3, 3)
        row["process_cpu_ms"] = round((edge1[2] - edge0[2]) * 1e3, 3)
        if edge0[3] is not None and edge1[3] is not None:
            row["main_runqueue_ms"] = round((edge1[3] - edge0[3]) / 1e6, 3)
    row["inner"] = collections.Counter(s[4] for s in inside).most_common(3)
    row["own"] = collections.Counter(s[5] for s in inside).most_common(3)
    return row


def one_run(cell, seed, seconds, devices, stall_s, period_s):
    calls = []  # (kind, start, end)
    marks = {}
    holder = {}
    real_time = harness.time

    def sleep(s):
        t = real_time.perf_counter()
        real_time.sleep(s)
        calls.append(("sleep", t, real_time.perf_counter()))

    def watch(system):
        holder["engine"] = getattr(system, "engine", None)
        poll, submit, reset = (system.poll, system.submit,
                               system.reset_tick_stats)

        def timed(kind, fn):
            def call(*a):
                t = real_time.perf_counter()
                out = fn(*a)
                calls.append((kind, t, real_time.perf_counter()))
                return out
            return call

        def reset_and_mark():
            reset()
            calls.clear()
            marks["window"] = real_time.perf_counter()

        system.poll = timed("poll", poll)
        system.submit = timed("submit", submit)
        system.reset_tick_stats = reset_and_mark
        return system

    sampler = Sampler(period_s)
    sampler.start()
    harness.time = types.SimpleNamespace(
        perf_counter=real_time.perf_counter, sleep=sleep)
    try:
        out = harness.run_cell(cell, seed, seconds, False,
                               real_time.perf_counter(), devices=devices,
                               system_override=watch, log=print)
    finally:
        harness.time = real_time
        sampler.stop()
    w0 = marks["window"]
    w1 = w0 + seconds
    calls = sorted((c for c in calls if w0 <= c[1] < w1),
                   key=lambda c: c[1])
    stalls = [_summary(k, a, b, sampler.samples, w0)
              for k, a, b in calls if k != "sleep" and b - a > stall_s]
    stalls += [_summary("harness", prev[2], nxt[1], sampler.samples, w0)
               for prev, nxt in zip(calls, calls[1:])
               if nxt[1] - prev[2] > stall_s]
    stalls.sort(key=lambda r: -r["ms"])
    inside = [s for s in sampler.samples if w0 <= s[0] <= w1]
    polls = sorted(b - a for k, a, b in calls if k == "poll")
    eng = holder.get("engine")
    tick_max_ms = ({k: round(getattr(eng, f"_m_{k}").max * 1e3, 3)
                    for k in ("prep", "dispatch", "fetch")}
                   if eng is not None else None)
    return {
        "cell": cell.name, "seed": seed, "correct": out["correct"],
        "traffic": cell.traffic, "attempted": out["attempted"],
        "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        "polls": len(polls),
        "poll_ms_p50_p99_max": [round(polls[int(q * (len(polls) - 1))]
                                      * 1e3, 3) for q in (0.5, 0.99, 1.0)]
        if polls else None,
        "tick_max_ms": tick_max_ms,
        "main_cpu_share": round((inside[-1][1] - inside[0][1])
                                / (inside[-1][0] - inside[0][0]), 4)
        if len(inside) > 1 else None,
        "process_cpu_share": round((inside[-1][2] - inside[0][2])
                                   / (inside[-1][0] - inside[0][0]), 4)
        if len(inside) > 1 else None,
        "stalls_over_ms": stall_s * 1e3,
        "stalls": len(stalls),
        "longest": stalls[:10],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--stall-ms", type=float, default=40)
    ap.add_argument("--period-ms", type=float, default=2)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = spec.cell(spec.load(ROOT), ROOT, args.cell)
    if args.rate:
        cell = dataclasses.replace(cell, name=f"{cell.name}@{args.rate}",
                                   traffic={**cell.traffic,
                                            "arrival": "poisson",
                                            "rate_per_s": args.rate})
    harness.enable_compile_cache(ROOT)
    devices = harness.check_chips(cell.chips)
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = one_run(cell, seed, args.seconds, devices,
                      args.stall_ms / 1e3, args.period_ms / 1e3)
        print(json.dumps(row), flush=True)
        if args.out:
            out = ROOT / args.out
            out.parent.mkdir(parents=True, exist_ok=True)
            with out.open("a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
