"""One traced run of a cell in this process, keeping its trace in the
compact form of ``trace.extract``; how
``tests/data/trace_c64_frames_max.json`` was recorded (a 0.25 s slice
of such a trace).

    python3 perfbench/tools/probe.py c64-frames-max --seed 5 \
        --seconds 8 --keep bench_out/trace.json
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

from perfbench import harness, spec  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=8)
    ap.add_argument("--keep", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(spec.load(ROOT), ROOT, args.cell)
    harness.enable_compile_cache(ROOT)
    devices = harness.check_chips(cell.chips)
    keep = ROOT / args.keep
    keep.parent.mkdir(parents=True, exist_ok=True)
    out = harness.run_cell(cell, args.seed, args.seconds, True, T0,
                           devices=devices, keep_trace=keep)
    for line in out.pop("_lines"):
        print(line)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
