"""Readings of the comparison's numbers, from which its limits are set:
the program over many seeds, the control (``control.ReferenceSystem``)
and each planted fault (``control.broken``) over a few, all through the
timed path at the cell's own size and load, in one process.

    python3 perfbench/tools/readings.py c64-frames-max --seconds 10 \
        --program 101-112 --control 201-203 --faults 301-303 \
        [--fault-kinds state_unchanged,half_batch]
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench import compare, control, harness, spec  # noqa: E402


def seeds(text: str):
    if not text:
        return []
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--control-seconds", type=float, default=10,
                    help="window of the control and fault runs, which "
                    "serve faster than the program")
    ap.add_argument("--program", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-kinds", default=",".join(control.FAULTS))
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = spec.cell(spec.load(ROOT), ROOT, args.cell)
    harness.enable_compile_cache(ROOT)
    devices = harness.check_chips(cell.chips)
    # every reading is printed whatever the configured limits are
    cell.config["limits"] = {k: float("inf") for k in compare.NUMBERS}

    def ref_system(system):
        return control.ReferenceSystem(cell.config, system.engine.params,
                                       "high", system.num_slots)

    plan = [("program", s, None) for s in seeds(args.program)]
    plan += [("control", s, ref_system) for s in seeds(args.control)]
    for f in args.fault_kinds.split(","):
        plan += [(f, s, control.broken(f)) for s in seeds(args.faults)]
    rows = []
    for kind, seed, override in plan:
        t = time.perf_counter()
        secs = args.seconds if kind == "program" else args.control_seconds
        out = harness.run_cell(cell, seed, secs, False,
                               time.perf_counter(), devices=devices,
                               system_override=override, log=lambda *a: None)
        row = {"kind": kind, "seed": seed, "attempted": out["attempted"],
               **{k: v["value"] for k, v in out["compared"].items()},
               **{k: v["value"] for k, v in out["metrics"].items()},
               "wall_s": round(time.perf_counter() - t, 2)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(ROOT / args.out, "a") as f:
            for row in rows:
                f.write(json.dumps({"cell": args.cell, **row}) + "\n")
    for kind in dict.fromkeys(r["kind"] for r in rows):
        sel = [r for r in rows if r["kind"] == kind]
        print(f"{kind}: " + ", ".join(
            f"{k} max {max(r[k] for r in sel):g} min "
            f"{min(r[k] for r in sel):g}" for k in compare.NUMBERS))
    return 0


if __name__ == "__main__":
    sys.exit(main())
