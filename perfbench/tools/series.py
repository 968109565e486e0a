"""Run cells several times, each run a process of its own, and report the
spread of every metric.

    python3 perfbench/tools/series.py --out bench_out/x.jsonl \
        --seconds 20 c64-frames-max:11,12,13 c64-frames-max:11,12,13/1

Each argument is ``<cell>:<seeds>[/<trace>]``.  Every run's result line
(and the tail of its standard error when it fails) is appended to
``--out``; the summary gives, per cell and metric, the median and the
spread: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median, over
all runs and with the run farthest from the median left out.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def spread(values):
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def spread_trimmed(values):
    if len(values) < 4:
        return spread(values)
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = values[:far] + values[far + 1:]
    return min(spread(values), spread(rest))


def run_one(cell, seed, seconds, trace, timeout):
    cmd = [sys.executable, "perfbench/run.py", "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out if isinstance(out, str) else out.decode()
        err = err if isinstance(err, str) else err.decode()
    wall = time.perf_counter() - t0
    last = out.strip().splitlines()[-1] if out.strip() else ""
    try:
        res = json.loads(last)
    except json.JSONDecodeError:
        res = None
    return {"cell": cell, "seed": seed, "trace": trace, "rc": rc,
            "wall_s": wall, "result": res,
            "stdout_tail": "\n".join(out.strip().splitlines()[-8:]),
            "stderr_tail": err[-3000:]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args(argv)
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    by_cell: dict = {}
    for spec_ in args.runs:
        cell, rest = spec_.split(":")
        trace = 0
        if "/" in rest:
            rest, t = rest.split("/")
            trace = int(t)
        for seed in [int(s) for s in rest.split(",")]:
            r = run_one(cell, seed, args.seconds, trace, args.timeout)
            with out.open("a") as f:
                f.write(json.dumps(r) + "\n")
            res = r["result"]
            print(f"{cell} seed {seed} trace {trace}: rc {r['rc']} "
                  f"wall {r['wall_s']:.1f}s correct "
                  f"{res and res['correct']} "
                  + (json.dumps({k: v["value"] for k, v in
                                 res["metrics"].items()}) if res else
                     r["stderr_tail"][-1500:]), flush=True)
            if res:
                print("   stdout: " + r["stdout_tail"].replace(
                    "\n", "\n   stdout: ")[:2500], flush=True)
                by_cell.setdefault((cell, trace), []).append(res)
    for (cell, trace), results in by_cell.items():
        names = sorted({k for r in results for k in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in results
                    if n in r["metrics"]]
            sp, spt = spread(vals), spread_trimmed(vals)
            print(f"SUMMARY {cell} trace {trace} {n}: n {len(vals)} "
                  f"median {statistics.median(vals):.6g} "
                  f"spread {sp if sp is None else round(sp, 5)} "
                  f"trimmed {spt if spt is None else round(spt, 5)} "
                  f"values {[round(v, 4) for v in vals]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
