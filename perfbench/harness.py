"""One run of one cell: set-up, a timed window of traffic, the comparison
with the reference, and the result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the weights on the device from the seed, renders the cell's
inputs, builds the system and warms up the shapes the traffic uses (one
slot-full of requests and one more).  The window then serves the mix for
``--seconds``: an open-loop schedule submits each request when it is due,
a backlog keeps the queue full; every request is timed from when it was
due.  No program may compile inside the window, and a run that leaves the
path its configuration states (the system's ``unsound``: a backend
demoted, a chunk retried) stops with an error.  With ``--trace 1`` the
last seconds of the window are traced and the per-layer metrics are read
from that trace and from the run; a metric the cell lists that reads
nothing is an error unless its reader says it ``MAY_BE_ABSENT``.  After
the window the requests still in flight are served, the device's memory
peak is read, the system is freed, and every request due in the window
is compared with the reference.

The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of that object.  Without an accelerator, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from perfbench import compare, spec, traffic as traffic_mod, trace as trace_mod

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
DRAIN_S = 60.0  # how long past the window a due request may take
REF_BLOCK = 128  # trains per reference call


class NoChip(RuntimeError):
    pass


class RunError(RuntimeError):
    pass


@dataclasses.dataclass
class Request:
    index: int
    due: float
    submitted: float = math.nan
    done: float = math.nan
    result: object = None


@dataclasses.dataclass
class Record:
    """What a metric reader reads."""

    cell: spec.Cell
    seconds: float  # the window's length, measured
    setup_s: float
    requests: list  # every Request due in the window
    completed_in_window: int
    tick: dict
    system: dict  # slots, chunk steps, capacity, address bytes, programs
    device_kind: str
    trace: dict | None = None

    def latencies_s(self) -> np.ndarray:
        """Due time to result of every request due in the window; a
        request that failed or never came is infinitely late."""
        return np.array([
            r.done - r.due if r.result is not None and r.result.ok
            else math.inf
            for r in self.requests
        ])


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of the exact values."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    k = max(int(math.ceil(q / 100.0 * v.size)) - 1, 0)
    return float(v[k])


def process_age_s() -> float | None:
    """Seconds since this process started, from the kernel's records."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19]) / ticks
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start
    except (OSError, ValueError, IndexError):
        return None


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def check_chips(chips: int):
    """The devices to run on; raises NoChip without an accelerator or
    with fewer than ``chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no accelerator: JAX platform is "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has "
                     f"{len(devices)}")
    return devices[:chips]


def derive_key(seed: int):
    import jax

    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2**31 - 1)))


class Tracer:
    """Traces the last ``span_s`` seconds of the window into a temporary
    directory, and reduces the trace once it is written."""

    def __init__(self, span_s: float, kernels):
        self.span_s = span_s
        self.kernels = kernels
        self.dir = None
        self._mark = None
        self.started_at = None

    def maybe_start(self, t: float, t_end: float) -> None:
        if self.dir is None and t >= t_end - self.span_s:
            import jax

            self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
            jax.profiler.start_trace(self.dir)
            self._mark = jax.profiler.TraceAnnotation("bench.traced")
            self._mark.__enter__()
            self.started_at = time.perf_counter()

    def stop(self) -> None:
        if self._mark is not None:
            import jax

            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._mark = None

    def reduce(self, keep: pathlib.Path | None = None) -> dict | None:
        if self.dir is None:
            return None
        try:
            paths = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
            if not paths:
                return None
            compact = trace_mod.extract(str(paths[-1]))
            if keep is not None:
                keep.write_text(json.dumps(compact))
            return trace_mod.reduce(compact, kernels=self.kernels)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def serve_window(system, source, traffic: dict, seconds: float, rng,
                 tracer: Tracer | None):
    """Serve the mix for ``seconds``; returns (requests due in the
    window, window start, window end, completions inside the window)."""
    open_loop = traffic_mod.is_open_loop(traffic)
    offsets = (traffic_mod.poisson_offsets(traffic, seconds, rng)
               if open_loop else None)
    if open_loop:
        picks = [source.draw() for _ in offsets]
    backlog = int(traffic.get("backlog", 0))
    requests: list = []
    by_rid: dict = {}
    done_in_window = 0

    def submit(index: int, due: float) -> None:
        req = Request(index=index, due=due)
        train = source.train(index)
        req.submitted = time.perf_counter()
        by_rid[system.submit(train)] = req
        requests.append(req)

    def collect(results, t_done):
        for r in results:
            req = by_rid.pop(r.request_id)
            req.result, req.done = r, t_done

    t0 = time.perf_counter()
    t_end = t0 + seconds
    i = 0
    while True:
        t = time.perf_counter()
        if t >= t_end:
            break
        if tracer is not None:
            tracer.maybe_start(t, t_end)
        with _annotate("bench.submit"):
            if open_loop:
                while i < len(offsets) and t0 + offsets[i] <= t:
                    submit(picks[i], t0 + offsets[i])
                    i += 1
            else:
                while system.queue_depth() < backlog:
                    submit(source.draw(), time.perf_counter())
        if not system.idle():
            with _annotate("bench.poll"):
                results = system.poll()
            t_done = time.perf_counter()
            collect(results, t_done)
            if t_done <= t_end:
                done_in_window += sum(r.ok for r in results)
        else:
            nxt = t0 + offsets[i] if open_loop and i < len(offsets) else t_end
            with _annotate("bench.sleep"):
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
    if tracer is not None:
        tracer.stop()
    # serve what is in flight; a request not back by then never came
    limit = time.perf_counter() + DRAIN_S
    while by_rid and not system.idle() and time.perf_counter() < limit:
        results = system.poll()
        collect(results, time.perf_counter())
    return requests, t0, t_end, done_in_window


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, *, devices=None, system_override=None,
             log=print, keep_trace: pathlib.Path | None = None) -> dict:
    """One run; returns the result object.  ``devices`` are the chips to
    run on (``check_chips``); ``system_override`` wraps the system for a
    test that breaks the timed path."""
    import jax

    from repro.analysis.contracts import RecompileDetector

    cfg, mix = cell.config, cell.traffic
    parts = {"imports_and_devices": time.perf_counter() - t_start}
    rng = np.random.default_rng(seed)

    t = time.perf_counter()
    ref = spec.named("references", cfg["reference"])
    params = ref.make_params(cfg, derive_key(seed))
    jax.block_until_ready(params)
    parts["weights"] = time.perf_counter() - t

    t = time.perf_counter()
    inputs = spec.named("inputs", mix["input"])
    source = inputs.Source(mix, int(cfg["num_steps"]),
                           int(cfg["layer_sizes"][0]), rng)
    parts["inputs"] = time.perf_counter() - t

    t = time.perf_counter()
    sys_mod = spec.named("systems", cfg["system"])
    system = sys_mod.System(cfg, params)
    if system_override is not None:
        system = system_override(system)
    # warm-up: one slot-full and one more request, so that every program
    # the window runs (admission, chunk, a slot's reuse) is compiled
    warm = [system.submit(source.train(source.draw()))
            for _ in range(system.num_slots + 1)]
    pending = set(warm)
    while pending:
        pending -= {r.request_id for r in system.poll()}
    system.reset_tick_stats()
    parts["system_and_warmup"] = time.perf_counter() - t
    age = process_age_s()
    setup_s = age if age is not None else time.perf_counter() - t_start
    log("setup parts (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in parts.items())
        + f"; setup_s {setup_s:.3f}")
    log(f"system: backend {system.backend}, {system.num_slots} slots, "
        f"chunk {system.chunk_steps} steps, capacity {system.capacity}; "
        f"input rate {source.mean_rate():.4f}")

    tracer = None
    if traced:
        tracer = Tracer(min(4.0, seconds / 2),
                        kernels=(sys_mod.PROGRAMS["kernel"],))
    fns = system.compiled_fns()
    with RecompileDetector(max_backend_compiles=0) as det:
        for name, fn in fns.items():
            det.track(name, fn, allowed=0)
        requests, t0, t_end, done_in = serve_window(
            system, source, mix, seconds, rng, tracer)
    compiles = det.unexpected()
    if compiles:
        raise RunError("compiled inside the window: " + "; ".join(compiles))
    tick = system.tick_stats()
    log(f"tick: {tick}")
    log(f"health: {system.health()}")
    devs = devices or jax.devices()[:cell.chips]
    unsound = system.unsound(devs[0].platform)
    if unsound:
        raise RunError("the run left its stated path: " + "; ".join(unsound))
    sysinfo = {
        "num_slots": system.num_slots,
        "chunk_steps": system.chunk_steps,
        "capacity": system.capacity,
        "addr_bytes": 2 if cfg["layer_sizes"][0] <= 32767 else 4,
        "programs": dict(sys_mod.PROGRAMS),
    }
    peak_mem = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devs)
    system.close()
    del system
    gc.collect()

    trace_summary = tracer.reduce(keep_trace) if tracer else None

    due = [r.index for r in requests]
    results = {r.index: r.result for r in requests if r.result is not None}
    t = time.perf_counter()
    ref_out = compare.reference_outputs(
        ref, params, source, due, cfg["precision"], REF_BLOCK)
    nums = compare.numbers(results, due, source, ref_out)
    correct = compare.verdict(nums, cfg["limits"]) and bool(due)
    log(f"reference: {len(due)} requests compared in "
        f"{time.perf_counter() - t:.3f} s")

    rec = Record(
        cell=cell, seconds=t_end - t0, setup_s=setup_s, requests=requests,
        completed_in_window=done_in, tick=tick, system=sysinfo,
        device_kind=devs[0].device_kind, trace=trace_summary)
    log(f"requests due {len(requests)}, completed in window {done_in}")
    if traffic_mod.is_open_loop(mix):
        lat = rec.latencies_s()
        log(f"latency from due p50 {percentile(lat, 50) * 1e3:.3f} ms, "
            f"p95 {percentile(lat, 95) * 1e3:.3f} ms, "
            f"p99 {percentile(lat, 99) * 1e3:.3f} ms")
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        reader = spec.reader(m["name"])
        value = reader.read(rec)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif not getattr(reader, "MAY_BE_ABSENT", False):
            raise RunError(f"metric {m['name']} of cell {cell.name} read "
                           f"nothing ({value!r})")
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak_mem,
    }
    out = {
        "correct": correct,
        "attempted": len(due),
        "failed": int(nums["missing"]),
        "metrics": metrics,
        "device": device,
    }
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        out["breakdown"] = {
            "device_ops": trace_summary["device_ops"],
            "idle_gaps": trace_summary["idle_gaps"],
        }
    out["compared"] = {
        k: {"value": nums[k], "limit": cfg["limits"][k]}
        for k in compare.NUMBERS
    }
    out["_lines"] = compare.lines(nums, cfg["limits"])
    return out


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"perfbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        cell = spec.cell(spec.load(ROOT), ROOT, args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    try:
        devices = check_chips(cell.chips)
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start, devices=devices)
    except RunError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 1
    lines = out.pop("_lines")
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0
