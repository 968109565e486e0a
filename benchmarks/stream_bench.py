"""Streaming-SNN serving benchmarks: rate sweep + open-loop async serving.

Default mode — event-driven vs dense execution across spike rates.
For each input spike rate r in [0, 1]:
  - ops: accumulator adds the AER path *measured* (events x fan_out) vs the
    dense path's fixed fan_in x fan_out x T — the paper's event-driven
    claim, verified with counted events instead of an assumed rate;
  - energy: both op counts priced with core.energy's Horowitz table;
  - time: wall time of the dense ``core.snn.forward`` vs the event-driven
    ``events.runtime.event_forward`` and the AER gather kernel vs the
    dense spike_matmul kernel (interpret mode on CPU — the op/energy
    scaling is the portable signal, kernel wall times are indicative only);
  - throughput: events/sec of the event-driven forward.

``--quick`` mode — open-loop async serving on the paper's 4096-512-2
collision config: Poisson arrivals submitted to the engine's
``submit()/poll()`` scheduler while chunks are in flight, per-request
deadlines (two deliberately already-due requests make the miss accounting
deterministic), p50/p99 latency, queue wait, a per-tick host-overhead
breakdown (host scheduling prep vs time in the chunk call vs the single
D2H stats fetch; with synchronous CPU dispatch the chunk-call bucket
includes device compute — see ``SNNStreamEngine.tick_breakdown``), and
a chunk-throughput cross-check against ``BENCH_snn.json`` (same config,
batch, chunk length).  The cross-check times the engine's *device-resident* chunk —
ring-sliced pre-staged event tables, the tick loop's real hot path —
against the BENCH ``overhauled_jnp`` figure, which still includes
per-chunk layer-0 extraction; a healthy resident engine therefore sits
*above* 1.0x, and the validation floor is 0.6x (raised from the
host-assembly era's 0.35x).

Since schema v3 the quick mode also exports the engine's observability
layer (``repro.obs``):

- per-request **latency / queue-wait / energy histograms** (log-bucket
  snapshots with p50/p90/p99) straight from the engine's metrics
  registry, next to the scalar percentiles they replace as evidence;
- a **dispatch attribution** that splits the tick's dominant
  ``dispatch_us`` bucket into host-enqueue vs device-compute wait (the
  blocking probe from ``repro.obs.profiler`` — ROADMAP item 2's open
  question, answered in-artifact);
- a measured **instrumentation overhead** bound (per-tick metrics+span
  recording cost vs the measured tick, asserted < 2% by ``--validate``);
- sidecar artifacts: the Chrome trace (``*_trace.json``,
  Perfetto-loadable per-request + tick-phase spans) and the full
  metrics snapshot (``*_metrics.json``), recorded under ``artifacts``.

Schema v4 adds the engine's *windowed* observability:

- a **timeseries** block from the engine's per-tick/per-submit
  ``TimeSeriesSampler``: sample accounting, trailing-window rates
  (events/s, ticks/s, windowed miss-rate) and a consistency table
  proving the sum of sampled counter deltas equals the lifetime counter
  values (the series was restarted at the post-warmup reset point, so
  the two must agree exactly);
- an **slo** verdict block — ``engine.health()``'s full multi-window
  burn-rate report over ``default_slos`` with the p99 target set to the
  run's deadline (the planted already-due requests guarantee a nonzero
  observed error rate on the deadline SLO);
- a third sidecar: the time series itself as JSONL
  (``*_timeseries.jsonl``), one object per sample.

Schema v5 adds the **fault_tolerance** block (``repro.faults``):

- the clean run's fault/shed counters, which must all be identically
  zero (no injector, no admission policy — the detection layer is a
  bit-exact no-op on healthy traffic);
- a seeded chaos probe on the same config: load shedding enabled
  (bounded admission queue + EDF feasibility shedder), two already-due
  requests (shed at admission instead of served-and-missed), two
  priority-1 requests (parked through queue overflow, then served),
  and a seeded ``FaultSchedule`` injected mid-run.  The block reports
  the shed rate, quarantine count vs the injector's own application
  log, the worst-case injection->quarantine recovery lag in ticks,
  retry/demotion counters and the chaos-vs-clean deadline miss rate.

Schema v6 adds the **static_analysis** block (``repro.analysis``):

- the full repro-lint pass (AST lint over ``src/repro``, Pallas kernel
  VMEM/SMEM budget + index-map bounds checks, AER address-width
  bounds) re-run in-process — the findings count must be zero;
- the recompile contract: the open-loop serving region runs inside a
  ``RecompileDetector`` tracking the chunk and admit functions
  (allowlist: zero — warmup owns the cold-start compile), and the
  engine's own ``steady_state_recompiles()`` counter must be zero.

Schema v7 adds the **recovery** block (crash safety):

- snapshot/warm-restart: the engine is snapshotted mid-flight
  (``snapshot_auto`` rotation, twice), the newest snapshot is
  byte-corrupted, and a fresh engine ``restore_latest_snapshot``s —
  the checksum must catch the corruption (fallback counter == injected
  corruptions) and the survivor's drained results must be bit-exact
  against an uninterrupted oracle (``resume_parity``); save/restore
  costs are reported in µs from the engine's own histograms;
- preemption: an urgent tight-deadline arrival on a full ``preempt=
  True`` engine must park a resident slot and later restore it
  bit-exactly; park/restore round-trip µs per slot are reported.

Emits ``stream_bench.json``; ``--validate`` structurally checks it (and
its sidecars) and fails on a chunk-throughput collapse vs the BENCH
baseline, missing/inconsistent histograms, instrumentation overhead
above 2% of a tick, a thin/inconsistent time series (< 20 samples, or
deltas that disagree with lifetime totals), a malformed SLO verdict,
nonzero clean-run fault counters, a chaos probe whose quarantine count
disagrees with its injection log (or that crashed, or recovered too
slowly).

Usage:  PYTHONPATH=src python -m benchmarks.stream_bench [--full]
        PYTHONPATH=src python -m benchmarks.stream_bench --quick [--json P]
        PYTHONPATH=src python -m benchmarks.stream_bench --validate P
   or:  PYTHONPATH=src python -m benchmarks.run stream
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.analysis import RecompileDetector
from repro.analysis.__main__ import run as analysis_run
from repro.core import energy, quant, snn
from repro.events import capacity as cap_mod
from repro.events import runtime
from repro.kernels import ops
from repro.obs import (
    default_slos,
    dispatch_attribution,
    tick_instrumentation_cost_us,
)

RATES = (0.0, 0.05, 0.1, 0.25, 0.5, 0.75, 1.0)

REPO_ROOT = Path(__file__).resolve().parents[1]
DEFAULT_JSON = REPO_ROOT / "stream_bench.json"
SCHEMA = "stream_bench/v7"
# per-request histograms carried since the v3 schema
HIST_KEYS = (
    "engine.request.latency_s",
    "engine.request.queue_wait_s",
    "engine.request.energy_pj",
)
# counters whose summed sampled deltas must equal their lifetime values
# (series restarted at the post-warmup reset, which zeroes them too)
CONSISTENCY_KEYS = (
    "engine.requests.submitted",
    "engine.requests.completed",
    "engine.requests.deadline_missed",
)
# the time series must actually have resolution: the quick run takes
# ~12 submits + >= 1 tick sample per poll, so 20 is a loose floor that
# still catches a sampler that silently stopped firing
MIN_TS_SAMPLES = 20
# per-tick observability recording must stay a rounding error next to
# the measured tick (acceptance: resident throughput regresses < 2%
# with instrumentation on)
MAX_OBS_OVERHEAD_FRAC = 0.02
# the engine's device-resident chunk skips the per-chunk layer-0
# extraction BENCH_snn's overhauled_jnp still pays, so a healthy engine
# sits above 1.0x; the floor catches collapse (a resident path that
# quietly fell back to host assembly lands well below it)
MIN_VS_BENCH = 0.6
# v5 chaos probe geometry: seeded fault schedule + bounded-queue
# shedding on the same collision config as the open-loop run
FT_SEED = 7
FT_FAULTS = 6
FT_REQUESTS = 12
FT_QUEUE_DEPTH = 4
# a quarantine must land within this many ticks of its injection
# (detection is one chunk behind the mutation, plus the one-deep stats
# pipeline and the drain loop's eager finishing) — the chaos test
# suite pins <= 6 at the same geometry, the artifact floor is looser
MAX_RECOVERY_TICKS = 8
# fault/shed counters that must be identically zero on the clean run
FT_CLEAN_ZERO_KEYS = (
    "engine.requests.shed",
    "engine.requests.parked",
    "engine.requests.quarantined",
    "engine.faults.chunk_retries",
    "engine.faults.backend_demoted",
    "engine.faults.injected",
)
# v7 recovery probe geometry: snapshot/warm-restart + preemption costs
# on the same collision config, with one seeded checkpoint corruption
RC_REQUESTS = 6
RC_CORRUPTIONS = 1


def _recovery_run(cfg, params, capacities) -> Dict:
    """Crash-safety probe for the v7 ``recovery`` block.

    Measures the engine's recovery-plane costs on the same collision
    config as the open-loop run: rotating snapshot writes on a loaded
    engine, warm-restart restore into a fresh engine (after a seeded
    byte-corruption of the newest snapshot — the restore must fall back
    to the previous one), and deadline-aware preemption park/restore
    round-trips.  Both the warm-restarted and the preempting engine are
    held to bit-exact parity with an uninterrupted oracle run
    (``resume_parity`` / ``preempt_parity``).
    """
    import shutil
    import tempfile
    import warnings as _warnings

    from repro.faults import corrupt_checkpoint
    from repro.serving.snn_engine import SNNStreamEngine, StreamRequest

    slots, Tc = 4, 5
    K = cfg.layer_sizes[0]
    rng = np.random.default_rng(2)
    trains = [
        (rng.random((cfg.num_steps, K)) < 0.2).astype(np.float32)
        for _ in range(RC_REQUESTS)
    ]

    def mk(preempt=False):
        return SNNStreamEngine(
            params, cfg, num_slots=slots, chunk_steps=Tc, backend="jnp",
            capacities=capacities, preempt=preempt,
        )

    def parity(results, oracle) -> bool:
        got = {r.request_id: r for r in results}
        if sorted(got) != sorted(oracle):
            return False
        return all(
            np.array_equal(got[i].spike_counts, oracle[i].spike_counts)
            and np.array_equal(
                got[i].events_per_layer, oracle[i].events_per_layer
            )
            and got[i].prediction == oracle[i].prediction
            and got[i].energy_pj == oracle[i].energy_pj
            for i in oracle
        )

    # oracle: the same engine instance serves the reference pass, then
    # (counters reset by run()) the snapshot pass — one chunk compile
    eng = mk()
    ref = eng.run([StreamRequest(spikes=t) for t in trains])
    # request ids keep counting up across passes on the same engine;
    # rebase every pass onto 0..n-1 window indices before comparing
    base = min(r.request_id for r in ref)
    oracle = {r.request_id - base: r for r in ref}

    snap_dir = tempfile.mkdtemp(prefix="stream_bench_recovery_")
    try:
        first_rid = eng._next_rid
        for t in trains:
            eng.submit(StreamRequest(spikes=t))
        eng.poll()
        eng.poll()
        # two rotation snapshots mid-flight (nothing has completed yet:
        # each window needs cfg.num_steps/Tc chunks plus the pipeline)
        eng.snapshot_auto(snap_dir)
        eng.poll()
        eng.snapshot_auto(snap_dir)
        src_snap = eng.metrics_snapshot()
        save_h = src_snap["engine.snapshot.save_s"]
        snapshot_us = 1e6 * save_h["sum"] / max(save_h["count"], 1)

        # corrupt the newest snapshot: restore_latest_snapshot must fall
        # back to the previous one in the rotation, loudly but cleanly
        corrupt_checkpoint(snap_dir, seed=FT_SEED)
        surv = mk()
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            restored_path = surv.restore_latest_snapshot(snap_dir)
        surv_snap = surv.metrics_snapshot()
        fallbacks = int(
            surv_snap["engine.faults.checkpoint_fallback"]["value"]
        )
        rest_h = surv_snap["engine.snapshot.restore_s"]
        restore_us = 1e6 * rest_h["sum"] / max(rest_h["count"], 1)
        resumed = [
            dataclasses_replace_rid(r, r.request_id - first_rid)
            for r in surv.drain()
        ]
        resume_parity = restored_path is not None and parity(
            resumed, oracle
        )
    finally:
        shutil.rmtree(snap_dir, ignore_errors=True)

    # preemption probe: fill every slot with loose windows, then land a
    # strictly tighter one — the loosest resident window parks, the
    # urgent one runs, the parked one resumes; all bit-exact
    ep = mk(preempt=True)
    pre_rid = ep._next_rid
    for t in trains[:slots]:
        ep.submit(StreamRequest(spikes=t))
    ep.poll()
    ep.submit(
        StreamRequest(spikes=trains[slots], priority=5, deadline_s=2.0)
    )
    for t in trains[slots + 1:]:
        ep.submit(StreamRequest(spikes=t))
    pre_results = [
        dataclasses_replace_rid(r, r.request_id - pre_rid)
        for r in ep.drain(timeout_s=120.0)
    ]
    ep_snap = ep.metrics_snapshot()
    park_h = ep_snap["engine.preempt.park_s"]
    unpark_h = ep_snap["engine.preempt.restore_s"]
    park_us = 1e6 * park_h["sum"] / max(park_h["count"], 1)
    unpark_us = 1e6 * unpark_h["sum"] / max(unpark_h["count"], 1)
    return {
        "requests": RC_REQUESTS,
        "snapshot_us": float(snapshot_us),
        "restore_us": float(restore_us),
        "snapshots_written": int(save_h["count"]),
        "injected_corruptions": RC_CORRUPTIONS,
        "checkpoint_fallbacks": fallbacks,
        "resume_parity": bool(resume_parity),
        "preemptions": int(ep_snap["engine.preempt.parked"]["value"]),
        "preempt_resumes": int(
            ep_snap["engine.preempt.resumed"]["value"]
        ),
        "preempt_park_us": float(park_us),
        "preempt_restore_us": float(unpark_us),
        "preempt_round_trip_us": float(park_us + unpark_us),
        "preempt_parity": parity(pre_results, oracle),
    }


def dataclasses_replace_rid(r, rid: int):
    """Rebase a StreamResult's request id onto a run-local index (the
    recovery probe reuses one engine across passes, so raw ids keep
    counting up)."""
    import dataclasses as _dc

    return _dc.replace(r, request_id=rid)


def _fault_tolerance_run(cfg, params, capacities) -> Dict:
    """Seeded chaos probe for the v5 ``fault_tolerance`` block.

    Same collision config as the open-loop run, but with the
    fault-tolerance plane switched on: a bounded admission queue (depth
    ``FT_QUEUE_DEPTH``) with EDF feasibility shedding, and a seeded
    :class:`~repro.faults.FaultSchedule` injected mid-run.  The
    submission pattern is deterministic by construction: two
    already-due requests (the feasibility shedder rejects them at
    admission instead of serving-and-missing), two bursts that overflow
    the bounded queue (the priority-1 request in each parks, the last
    priority-0 one sheds), the rest served.  Returns the measured chaos
    sub-block; ``crashes`` counts loop-level exceptions (must be 0).
    """
    import dataclasses as _dc

    from repro.faults import (
        AdmissionPolicy,
        FaultInjector,
        FaultSchedule,
        RetryPolicy,
    )
    from repro.serving.snn_engine import SNNStreamEngine, StreamRequest

    slots, Tc = 4, 5
    n_req = FT_REQUESTS
    K = cfg.layer_sizes[0]
    rng = np.random.default_rng(1)
    trains = [
        (rng.random((cfg.num_steps, K)) < 0.2).astype(np.float32)
        for _ in range(n_req)
    ]
    eng = SNNStreamEngine(
        params, cfg, num_slots=slots, chunk_steps=Tc, backend="jnp",
        capacities=capacities,
        admission=AdmissionPolicy(max_queue_depth=FT_QUEUE_DEPTH),
        # budget above the worst-case pile-up of same-tick injected
        # exceptions: generated schedules stay transient (see
        # FaultSchedule.generate), so the supervisor always recovers
        retry=RetryPolicy(max_retries=8, backoff_s=0.0),
    )
    # warm without the injector: pays the chunk compile and gives the
    # feasibility shedder its measured tick-rate evidence
    eng.run([StreamRequest(spikes=trains[0])])
    eng.reset_tick_stats()
    eng.metrics.reset(prefix="engine.request")
    eng.metrics.reset(prefix="engine.faults")
    eng.metrics.reset(prefix="engine.episode")
    # schedule the faults over the post-warm tick horizon
    t0 = eng._tick_index
    base = FaultSchedule.generate(
        FT_SEED, FT_FAULTS, ticks=12, num_slots=slots,
        num_layers=cfg.num_layers,
        kinds=("nan_membrane", "corrupt_ring", "chunk_exception"),
    )
    schedule = FaultSchedule(
        faults=tuple(
            _dc.replace(f, tick=f.tick + t0) for f in base.faults
        ),
        seed=FT_SEED,
    )
    inj = FaultInjector(schedule)
    eng.injector = inj

    # generous budget so the feasibility shedder only fires on the two
    # planted already-due requests (a tight budget is *legitimately*
    # sheddable when the trailing-window rate evidence straddles it,
    # which would make the artifact's shed split timing-dependent)
    deadline_s = 10.0

    def req(i, *, deadline=deadline_s, priority=0):
        return StreamRequest(
            spikes=trains[i], deadline_s=deadline, priority=priority
        )

    bursts = [
        # burst 1: 2 already-due (feasibility sheds at pop) + 2 normal
        # fill the queue; the priority-1 request parks, the last sheds
        [req(0, deadline=0.0), req(1, deadline=0.0), req(2), req(3),
         req(4, priority=1), req(5)],
        # burst 2: queue refills; same park/shed tail
        [req(6), req(7), req(8), req(9), req(10, priority=1), req(11)],
    ]
    results, crashes = [], 0
    try:
        for burst in bursts:
            for r in burst:
                eng.submit(r)
            results.extend(eng.poll())
        results.extend(eng.drain(timeout_s=120.0))
    except Exception:  # chaos must never crash the serving loop
        crashes = 1

    snap = eng.metrics_snapshot()
    ok = [r for r in results if r.disposition == "ok"]
    applied_state = [
        rec for rec in inj.applied
        if rec["kind"] in ("nan_membrane", "corrupt_ring")
    ]
    applied_tick = {rec["rid"]: rec["tick"] for rec in applied_state}
    recovery = [
        ev["tick"] - applied_tick[ev["rid"]]
        for ev in eng.fault_events if ev["rid"] in applied_tick
    ]
    chaos_miss = (
        sum(r.deadline_missed for r in ok) / len(ok) if ok else 0.0
    )
    return {
        "requests": n_req,
        "schedule_seed": FT_SEED,
        "schedule_len": len(schedule),
        "injected_faults": len(inj.applied),
        "served_ok": len(ok),
        "shed": sum(r.disposition == "shed" for r in results),
        "parked_served": int(sum(r.parked for r in ok)),
        "quarantined": sum(
            r.disposition == "quarantined" for r in results
        ),
        "quarantine_expected": len(
            {rec["rid"] for rec in applied_state}
        ),
        "shed_rate": float(eng.shed_rate()),
        "deadline_miss_rate": float(chaos_miss),
        "recovery_ticks_max": max(recovery) if recovery else None,
        "chunk_retries": float(
            snap["engine.faults.chunk_retries"]["value"]
        ),
        "backend_demotions": float(
            snap["engine.faults.backend_demoted"]["value"]
        ),
        "crashes": crashes,
        "diagnosis": eng.health()["diagnosis"]["verdict"],
    }


def open_loop_run(
    quick: bool = True, json_path: Optional[Path] = None
) -> Dict:
    """Open-loop async serving on the collision config -> stream_bench.json.

    Matches BENCH_snn.json's quick geometry (4096-512-2, 4 slots, Tc=5,
    jnp backend) so the chunk-throughput cross-check compares like with
    like.
    """
    from repro.configs.collision_snn import CONFIG as cfg
    from repro.serving.snn_engine import SNNStreamEngine, StreamRequest

    json_path = Path(json_path) if json_path else DEFAULT_JSON
    slots, Tc = 4, 5
    n_req = 12 if quick else 32
    arrival_rate = 40.0 if quick else 60.0
    deadline_s = 2.0
    n_hopeless = 2  # already-due deadlines: deterministic misses

    params = snn.init_params(jax.random.PRNGKey(0), cfg)
    K = cfg.layer_sizes[0]
    rng = np.random.default_rng(0)
    trains = [
        (rng.random((cfg.num_steps, K)) < 0.2).astype(np.float32)
        for _ in range(n_req)
    ]
    # autotuned capacities, like BENCH_snn.json's overhauled_jnp path —
    # the chunk cross-check below must compare like with like
    plan = cap_mod.autotune(
        params, cfg, jnp.asarray(np.stack(trains, axis=1)),
        percentile=100.0, safety=1.2, align=128,
    )
    # SLOs at the run's own scale: p99 target = the per-request deadline
    engine = SNNStreamEngine(
        params, cfg, num_slots=slots, chunk_steps=Tc, backend="jnp",
        capacities=plan.capacities,
        slos=default_slos(p99_target_s=deadline_s),
        trace_capacity=8192,
    )
    reqs = [
        StreamRequest(
            spikes=t,
            deadline_s=0.0 if i < n_hopeless else deadline_s,
        )
        for i, t in enumerate(trains)
    ]

    # warm the compiled chunk so open-loop latencies measure steady
    # state; drop the warmup's tick timings (first tick pays compile),
    # request histograms, lifetime counters and spans alike
    engine.run([StreamRequest(spikes=trains[0])])
    engine.reset_tick_stats()
    engine.metrics.reset(prefix="engine.request")
    engine.trace.clear()
    # re-baseline the time series at the same reset point, so summed
    # sampled deltas must equal the lifetime counter values exactly
    engine.timeseries.restart()

    arrivals = np.cumsum(rng.exponential(1.0 / arrival_rate, n_req))
    results, i = [], 0
    # v6: the recompile contract — post-warmup, the open-loop region
    # must compile *nothing*: the chunk and the admit path were both
    # compiled by the warmup request, so any cache growth here means a
    # shape-unstable submit/tick path (the serving hazard repro-lint
    # exists to catch)
    detector = RecompileDetector()
    with detector:
        detector.track("chunk", engine._chunk, allowed=0)
        detector.track("admit_spikes", engine._admit_spikes_fn, allowed=0)
        start = time.perf_counter()
        while i < n_req or not engine.idle():
            now = time.perf_counter() - start
            while i < n_req and arrivals[i] <= now:
                engine.submit(reqs[i])
                i += 1
            if engine.idle() and i < n_req:
                time.sleep(
                    max(arrivals[i] - (time.perf_counter() - start), 0.0)
                )
                continue
            results.extend(engine.poll())
        elapsed_s = time.perf_counter() - start

    # aggregate over the collected results, not the engine's episode
    # counters: an arrival gap longer than the service time drains the
    # engine mid-trace, closing one episode and resetting counters at the
    # next submit — the trace-wide numbers must span every episode
    lat_ms = np.array([r.latency_s for r in results]) * 1e3
    wait_ms = np.array([r.queue_wait_s for r in results]) * 1e3
    miss_rate = sum(r.deadline_missed for r in results) / len(results)
    events_total = float(
        sum(r.events_per_layer.sum() for r in results)
    )

    # chunk-throughput cross-check: the engine's compiled device-resident
    # chunk on a fully-active micro-batch of staged rings — the tick
    # loop's real hot path — vs BENCH_snn.json's overhauled_jnp figure
    # (same config / batch / chunk length, but BENCH's path still pays
    # per-chunk layer-0 extraction, so healthy is > 1.0x)
    staged = engine.staged_chunk_args(trains[:slots])
    t_chunk = time_fn(
        engine.chunk_for_timing(), *staged,
        warmup=1, iters=3 if quick else 5,
    )
    steps_per_s = Tc * slots / (t_chunk * 1e-6)
    vs_bench = None
    bench_path = REPO_ROOT / "BENCH_snn.json"
    if bench_path.exists():
        ref = json.loads(bench_path.read_text())
        vs_bench = (
            steps_per_s / ref["paths"]["overhauled_jnp"]["steps_per_s"]
        )

    # dispatch attribution: split the tick's dominant dispatch_us bucket
    # (time in the chunk call) into host enqueue vs device-compute wait
    # — the ROADMAP item-2 question, answered with a blocking probe on
    # the very chunk the cross-check just timed
    attribution = dispatch_attribution(
        engine.chunk_for_timing(), *staged,
        warmup=1, iters=3 if quick else 5,
    )

    # instrumentation overhead: measured per-tick metrics+span recording
    # cost (scratch instruments, exact op mix of one tick) against the
    # run's measured mean tick
    tb = engine.tick_breakdown()
    mean_tick_us = (
        tb["host_prep_us"] + tb["dispatch_us"] + tb["stats_fetch_us"]
    )
    obs_us = tick_instrumentation_cost_us(num_slots=slots)
    obs_overhead = {
        "per_tick_obs_us": obs_us,
        "mean_tick_us": mean_tick_us,
        "overhead_frac": obs_us / max(mean_tick_us, 1e-9),
    }

    # v4: windowed time-series summary + counter-delta consistency.
    # The series was restarted at the post-warmup reset (which zeroed
    # the engine.request* counters too), so for never-reset lifetime
    # counters sum-of-deltas must equal the lifetime value exactly.
    ts = engine.timeseries
    snap = engine.metrics_snapshot()
    win_s = 1.0
    timeseries_block = {
        "samples": len(ts),
        "span_s": ts.span_s(),
        "window_s": win_s,
        "windowed": {
            "miss_rate": engine.windowed_miss_rate(win_s),
            "events_per_s": ts.rate("engine.episode.events", win_s),
            "ticks_per_s": ts.rate("engine.tick.dispatch_s.count", win_s),
            "requests_per_s": ts.rate("engine.requests.completed", win_s),
        },
        "consistency": {
            k: {
                "series_total": ts.cum(k),
                "lifetime": float(snap[k]["value"]),
            }
            for k in CONSISTENCY_KEYS
        },
    }

    # v4: the SLO verdict — engine.health() runs the multi-window
    # burn-rate evaluation and publishes the engine.slo.status gauge
    slo_report = engine.health()

    # v5: fault-tolerance evidence.  The clean run above had no
    # injector and no admission policy, so its fault/shed counters must
    # all be zero — recorded and validated as such; the chaos probe is
    # a second, seeded run on the same config with shedding on
    fault_tolerance = {
        "clean": {
            "counters": {
                k: float(snap[k]["value"]) for k in FT_CLEAN_ZERO_KEYS
            },
            "deadline_miss_rate": float(miss_rate),
        },
        "chaos": _fault_tolerance_run(cfg, params, plan.capacities),
    }
    # shedding-on chaos converts hopeless deadlines into sheds, so the
    # chaos miss rate sits *below* the clean run's planted-miss rate
    fault_tolerance["miss_rate_delta"] = (
        fault_tolerance["chaos"]["deadline_miss_rate"]
        - fault_tolerance["clean"]["deadline_miss_rate"]
    )

    # v7: crash-safety evidence — snapshot/warm-restart costs and
    # parity, checkpoint-corruption fallback, preemption round-trips
    recovery = _recovery_run(cfg, params, plan.capacities)

    # v6: the static-analysis contract.  The full repro-lint pass
    # (AST lint over src/repro + kernel VMEM/SMEM budgets + AER bounds)
    # runs in-process and must come back clean, and the open-loop
    # region above must have been recompile-free — both validated
    sa_report = analysis_run()
    static_analysis = {
        "lint_findings": sa_report["counts"]["findings"],
        "lint_suppressed": sa_report["counts"]["suppressed"],
        "kernel_vmem_bytes": {
            p["kernel"]: p["vmem_bytes"] for p in sa_report["kernels"]
        },
        "steady_state_recompiles": engine.steady_state_recompiles(),
        "recompile_detector": detector.report(),
    }

    # sidecar artifacts next to the JSON: the Perfetto-loadable span
    # trace, the full metrics snapshot and the time-series JSONL (CI
    # uploads all three)
    trace_path = json_path.with_name(json_path.stem + "_trace.json")
    metrics_path = json_path.with_name(json_path.stem + "_metrics.json")
    ts_path = json_path.with_name(json_path.stem + "_timeseries.jsonl")
    engine.export_trace(trace_path)
    engine.metrics.write_json(metrics_path)
    ts.write_jsonl(ts_path)
    doc = {
        "schema": SCHEMA,
        "mode": "quick" if quick else "full",
        "config": {
            "layer_sizes": list(cfg.layer_sizes),
            "num_steps": cfg.num_steps,
            "chunk_steps": Tc,
            "num_slots": slots,
            "requests": n_req,
            "arrival_rate_rps": arrival_rate,
            "deadline_ms": deadline_s * 1e3,
            "hopeless_deadlines": n_hopeless,
            "capacities": [int(c) for c in plan.capacities],
        },
        "open_loop": {
            "served": len(results),
            "p50_latency_ms": float(np.percentile(lat_ms, 50)),
            "p99_latency_ms": float(np.percentile(lat_ms, 99)),
            "mean_queue_wait_ms": float(wait_ms.mean()),
            "deadline_miss_rate": float(miss_rate),
            "events_per_s": events_total / max(elapsed_s, 1e-9),
        },
        "chunk": {
            "us_per_chunk": t_chunk,
            "steps_per_s": steps_per_s,
            "vs_bench_overhauled_jnp": vs_bench,
        },
        # per-request histograms straight from the engine's metrics
        # registry (log buckets, exact count/sum/min/max, approximate
        # percentiles) — warmup was reset out, so counts == served
        "histograms": {k: snap[k] for k in HIST_KEYS},
        # measured per-tick breakdown of the open-loop run above — the
        # evidence future PRs read to see where serving time goes.  NB
        # dispatch_us is time *in* the chunk call: with synchronous
        # dispatch (CPU) it includes the device compute wait; host
        # scheduling overhead proper is host_prep_us, and the D2H cost
        # is stats_fetch_us (see SNNStreamEngine.tick_breakdown)
        "host_overhead": tb,
        # the measured split of dispatch_us: host enqueue (the only part
        # that is actually host overhead) vs device-compute wait
        "dispatch_attribution": attribution,
        "obs_overhead": obs_overhead,
        # v4: windowed rates + delta/lifetime consistency proof
        "timeseries": timeseries_block,
        # v4: the full multi-window burn-rate report (engine.health())
        "slo": slo_report,
        # v5: clean-run zero counters + the seeded chaos probe
        "fault_tolerance": fault_tolerance,
        # v6: repro-lint pass + recompile contract over the open loop
        "static_analysis": static_analysis,
        # v7: snapshot/warm-restart + preemption probe
        "recovery": recovery,
        "artifacts": {
            "trace": trace_path.name,
            "metrics": metrics_path.name,
            "timeseries": ts_path.name,
        },
    }
    json_path.write_text(json.dumps(doc, indent=2) + "\n")
    emit(
        "stream_bench/open_loop", float(np.percentile(lat_ms, 50)) * 1e3,
        f"p99_ms={np.percentile(lat_ms, 99):.1f};"
        f"miss_rate={doc['open_loop']['deadline_miss_rate']:.3f};"
        f"events_per_s={doc['open_loop']['events_per_s']:.0f}",
    )
    emit(
        "stream_bench/chunk", t_chunk,
        f"steps_per_s={steps_per_s:.1f};"
        f"vs_bench={vs_bench if vs_bench is None else round(vs_bench, 3)};"
        f"json={json_path}",
    )
    emit(
        "stream_bench/dispatch_attribution", attribution["total_us"],
        f"host_enqueue_us={attribution['host_enqueue_us']:.0f};"
        f"device_wait_frac={attribution['device_wait_frac']:.3f};"
        f"obs_overhead_frac={obs_overhead['overhead_frac']:.5f}",
    )
    emit(
        "stream_bench/slo", float(slo_report["status_code"]),
        f"status={slo_report['status']};"
        f"samples={timeseries_block['samples']};"
        f"windowed_miss_rate="
        f"{timeseries_block['windowed']['miss_rate']:.3f}",
    )
    chaos = fault_tolerance["chaos"]
    emit(
        "stream_bench/fault_tolerance", float(chaos["shed_rate"]),
        f"quarantined={chaos['quarantined']};"
        f"recovery_ticks_max={chaos['recovery_ticks_max']};"
        f"chaos_miss_rate={chaos['deadline_miss_rate']:.3f};"
        f"crashes={chaos['crashes']};"
        f"diagnosis={chaos['diagnosis']}",
    )
    emit(
        "stream_bench/recovery", float(recovery["restore_us"]),
        f"snapshot_us={recovery['snapshot_us']:.0f};"
        f"preemptions={recovery['preemptions']};"
        f"park_round_trip_us={recovery['preempt_round_trip_us']:.0f};"
        f"resume_parity={recovery['resume_parity']};"
        f"fallbacks={recovery['checkpoint_fallbacks']}",
    )
    return doc


def validate(path: Path) -> List[str]:
    """Structural validation of a stream_bench.json; returns error strings."""
    errors: List[str] = []
    try:
        doc = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"unreadable JSON: {e}"]
    if not isinstance(doc, dict):
        return ["top level is not an object"]
    if doc.get("schema") != SCHEMA:
        errors.append(f"schema {doc.get('schema')!r} != {SCHEMA!r}")
    ol = doc.get("open_loop", {})
    for k in ("p50_latency_ms", "p99_latency_ms", "events_per_s"):
        v = ol.get(k)
        if not isinstance(v, (int, float)) or not v > 0:
            errors.append(f"open_loop.{k} not a positive number: {v!r}")
    wait = ol.get("mean_queue_wait_ms")
    if not isinstance(wait, (int, float)) or wait < 0:
        errors.append(f"open_loop.mean_queue_wait_ms invalid: {wait!r}")
    served = ol.get("served")
    want = doc.get("config", {}).get("requests")
    if served != want:
        errors.append(f"open_loop.served {served!r} != requested {want!r}")
    miss = ol.get("deadline_miss_rate")
    # the run plants already-due deadlines, so the rate must be nonzero
    if not isinstance(miss, (int, float)) or not (0.0 < miss <= 1.0):
        errors.append(
            f"open_loop.deadline_miss_rate not in (0, 1]: {miss!r}"
        )
    chunk = doc.get("chunk", {})
    for k in ("us_per_chunk", "steps_per_s"):
        v = chunk.get(k)
        if not isinstance(v, (int, float)) or not v > 0:
            errors.append(f"chunk.{k} not a positive number: {v!r}")
    vs = chunk.get("vs_bench_overhauled_jnp")
    if vs is None:
        errors.append(
            "chunk.vs_bench_overhauled_jnp is null — generate "
            "BENCH_snn.json (benchmarks.run --quick) before this bench"
        )
    elif not isinstance(vs, (int, float)) or vs < MIN_VS_BENCH:
        errors.append(
            f"chunk throughput regression: engine chunk at {vs!r}x the "
            f"BENCH_snn.json overhauled_jnp path (floor {MIN_VS_BENCH})"
        )
    host = doc.get("host_overhead", {})
    for k in ("host_prep_us", "dispatch_us", "stats_fetch_us"):
        v = host.get(k)
        if not isinstance(v, (int, float)) or v < 0:
            errors.append(f"host_overhead.{k} invalid: {v!r}")
    ticks = host.get("ticks")
    if not isinstance(ticks, int) or ticks < 1:
        errors.append(f"host_overhead.ticks invalid: {ticks!r}")
    if host.get("pipeline_depth") != 1:
        errors.append(
            "host_overhead.pipeline_depth != 1 — the open-loop bench "
            "must exercise the pipelined tick"
        )
    # v3: per-request histograms, internally consistent and covering
    # every served request
    hists = doc.get("histograms", {})
    for key in HIST_KEYS:
        h = hists.get(key)
        if not isinstance(h, dict) or h.get("type") != "histogram":
            errors.append(f"histograms.{key} missing or not a histogram")
            continue
        count = h.get("count")
        if count != served:
            errors.append(
                f"histograms.{key}.count {count!r} != served {served!r}"
            )
        accounted = (
            h.get("underflow", 0)
            + h.get("overflow", 0)
            + sum(c for _, c in h.get("buckets", []))
        )
        if accounted != count:
            errors.append(
                f"histograms.{key}: bucket counts sum to {accounted}, "
                f"count says {count}"
            )
        p50, p90, p99 = h.get("p50"), h.get("p90"), h.get("p99")
        if not all(
            isinstance(p, (int, float)) and p > 0
            for p in (p50, p90, p99)
        ) or not (p50 <= p90 <= p99):
            errors.append(
                f"histograms.{key}: percentiles missing or not "
                f"monotone: p50={p50!r} p90={p90!r} p99={p99!r}"
            )
    # v3: measured host-enqueue vs device-wait split of dispatch_us
    att = doc.get("dispatch_attribution", {})
    enq, wait, total = (
        att.get("host_enqueue_us"),
        att.get("device_wait_us"),
        att.get("total_us"),
    )
    if not isinstance(enq, (int, float)) or not enq > 0:
        errors.append(f"dispatch_attribution.host_enqueue_us: {enq!r}")
    if not isinstance(wait, (int, float)) or wait < 0:
        errors.append(f"dispatch_attribution.device_wait_us: {wait!r}")
    if (
        not isinstance(total, (int, float))
        or not total > 0
        or abs(total - (enq or 0) - (wait or 0)) > 0.05 * total
    ):
        errors.append(
            f"dispatch_attribution.total_us {total!r} inconsistent with "
            f"enqueue {enq!r} + wait {wait!r}"
        )
    if not isinstance(att.get("verdict"), str):
        errors.append("dispatch_attribution.verdict missing")
    # v3: instrumentation must cost < 2% of a measured tick
    obs = doc.get("obs_overhead", {})
    frac = obs.get("overhead_frac")
    if not isinstance(frac, (int, float)) or frac < 0:
        errors.append(f"obs_overhead.overhead_frac invalid: {frac!r}")
    elif frac >= MAX_OBS_OVERHEAD_FRAC:
        errors.append(
            f"instrumentation overhead {frac:.4f} of a tick >= "
            f"{MAX_OBS_OVERHEAD_FRAC} budget "
            f"(per_tick_obs_us={obs.get('per_tick_obs_us')!r})"
        )
    # v4: the time series must be dense enough and its deltas must
    # reconcile with the lifetime counters
    ts = doc.get("timeseries", {})
    n_samples = ts.get("samples")
    if not isinstance(n_samples, int) or n_samples < MIN_TS_SAMPLES:
        errors.append(
            f"timeseries.samples {n_samples!r} < {MIN_TS_SAMPLES} — "
            f"sampler not firing per tick/submit"
        )
    if not isinstance(ts.get("span_s"), (int, float)) or ts["span_s"] <= 0:
        errors.append(f"timeseries.span_s invalid: {ts.get('span_s')!r}")
    wnd = ts.get("windowed", {})
    for k in ("miss_rate", "events_per_s", "ticks_per_s", "requests_per_s"):
        v = wnd.get(k)
        if not isinstance(v, (int, float)) or v < 0:
            errors.append(f"timeseries.windowed.{k} invalid: {v!r}")
    cons = ts.get("consistency", {})
    for k in CONSISTENCY_KEYS:
        c = cons.get(k)
        if not isinstance(c, dict):
            errors.append(f"timeseries.consistency.{k} missing")
            continue
        st, lt = c.get("series_total"), c.get("lifetime")
        if (
            not isinstance(st, (int, float))
            or not isinstance(lt, (int, float))
            or abs(st - lt) > 1e-6 * max(abs(lt), 1.0)
        ):
            errors.append(
                f"timeseries.consistency.{k}: sum of sampled deltas "
                f"{st!r} != lifetime counter {lt!r}"
            )
    # v4: the SLO verdict block is a full burn-rate report
    slo = doc.get("slo", {})
    status = slo.get("status")
    if status not in ("healthy", "degraded", "breach"):
        errors.append(f"slo.status invalid: {status!r}")
    codes = {"healthy": 0, "degraded": 1, "breach": 2}
    if slo.get("status_code") != codes.get(status):
        errors.append(
            f"slo.status_code {slo.get('status_code')!r} does not encode "
            f"status {status!r}"
        )
    slo_entries = {
        s.get("name"): s for s in slo.get("slos", [])
        if isinstance(s, dict)
    }
    for name in ("deadline_misses", "latency_p99"):
        if name not in slo_entries:
            errors.append(f"slo report missing the {name!r} SLO")
    dm = slo_entries.get("deadline_misses")
    if dm is not None:
        # the run plants already-due deadlines: the whole-series error
        # rate on the deadline SLO must be observed as nonzero
        er = dm.get("observed_error_rate")
        if not isinstance(er, (int, float)) or not er > 0:
            errors.append(
                f"deadline_misses SLO observed_error_rate {er!r} not > 0 "
                f"despite planted already-due deadlines"
            )
    for name, entry in slo_entries.items():
        rules = entry.get("rules")
        if not isinstance(rules, list) or not rules:
            errors.append(f"slo {name!r} has no burn-rate rules")
            continue
        for r in rules:
            for k in ("long_burn_rate", "short_burn_rate"):
                v = r.get(k, "absent")
                if v is not None and (
                    not isinstance(v, (int, float)) or v < 0
                ):
                    errors.append(f"slo {name!r} rule {k} invalid: {v!r}")
            if not isinstance(r.get("fired"), bool):
                errors.append(f"slo {name!r} rule missing 'fired'")
    # v5: fault tolerance — clean counters identically zero; the chaos
    # probe quarantined exactly its injected faults, recovered within
    # the tick bound, accounted every request, and never crashed
    ft = doc.get("fault_tolerance", {})
    counters = ft.get("clean", {}).get("counters", {})
    for k in FT_CLEAN_ZERO_KEYS:
        v = counters.get(k)
        if v != 0:
            errors.append(
                f"fault_tolerance.clean.counters[{k!r}] = {v!r} != 0 "
                f"on a fault-free run"
            )
    chaos = ft.get("chaos", {})
    n = chaos.get("requests")
    if not isinstance(n, int) or n < 1:
        errors.append(f"fault_tolerance.chaos.requests invalid: {n!r}")
    if chaos.get("crashes") != 0:
        errors.append(
            f"fault_tolerance.chaos.crashes = "
            f"{chaos.get('crashes')!r} — the chaos probe crashed"
        )
    inj_n = chaos.get("injected_faults")
    if not isinstance(inj_n, int) or inj_n < 1:
        errors.append(
            f"fault_tolerance.chaos.injected_faults {inj_n!r} < 1 — "
            f"the seeded schedule never fired"
        )
    q, qe = chaos.get("quarantined"), chaos.get("quarantine_expected")
    if not isinstance(qe, int) or qe < 1:
        errors.append(
            f"fault_tolerance.chaos.quarantine_expected {qe!r} < 1 — "
            f"no state/ring fault was ever applied"
        )
    if q != qe:
        errors.append(
            f"fault_tolerance.chaos quarantined {q!r} != faulted "
            f"requests {qe!r} — quarantine must hit exactly the "
            f"faulted slots"
        )
    acc = (chaos.get("served_ok"), chaos.get("shed"), q)
    if not all(isinstance(x, int) for x in acc) or sum(acc) != n:
        errors.append(
            f"fault_tolerance.chaos dispositions ok+shed+quarantined "
            f"{acc!r} do not sum to requests {n!r}"
        )
    sr = chaos.get("shed_rate")
    if not isinstance(sr, (int, float)) or not (0.0 <= sr <= 1.0):
        errors.append(
            f"fault_tolerance.chaos.shed_rate invalid: {sr!r}"
        )
    elif isinstance(chaos.get("shed"), int) and chaos["shed"] > 0 \
            and not sr > 0:
        errors.append(
            "fault_tolerance.chaos.shed_rate is 0 despite sheds"
        )
    mr = chaos.get("deadline_miss_rate")
    if not isinstance(mr, (int, float)) or not (0.0 <= mr <= 1.0):
        errors.append(
            f"fault_tolerance.chaos.deadline_miss_rate invalid: {mr!r}"
        )
    rt = chaos.get("recovery_ticks_max")
    if isinstance(q, int) and q > 0 and (
        not isinstance(rt, int) or not (1 <= rt <= MAX_RECOVERY_TICKS)
    ):
        errors.append(
            f"fault_tolerance.chaos.recovery_ticks_max {rt!r} outside "
            f"[1, {MAX_RECOVERY_TICKS}]"
        )
    if chaos.get("diagnosis") not in (
        "faulty", "overloaded", "breaching", "nominal"
    ):
        errors.append(
            f"fault_tolerance.chaos.diagnosis invalid: "
            f"{chaos.get('diagnosis')!r}"
        )
    # v6: static-analysis + recompile contract
    sa = doc.get("static_analysis", {})
    if not isinstance(sa, dict) or not sa:
        errors.append("static_analysis block missing")
    else:
        lf = sa.get("lint_findings")
        if lf != 0:
            errors.append(
                f"static_analysis.lint_findings = {lf!r} != 0 — the tree "
                "must lint clean (fix or suppress with a reason)"
            )
        rc = sa.get("steady_state_recompiles")
        if rc != 0:
            errors.append(
                f"static_analysis.steady_state_recompiles = {rc!r} != 0 — "
                "a dispatch path recompiled mid-serve"
            )
        det = sa.get("recompile_detector", {})
        tracked = det.get("tracked", {}) if isinstance(det, dict) else {}
        if not tracked:
            errors.append("static_analysis.recompile_detector.tracked empty")
        for name, rep in tracked.items():
            unexpected = rep.get("unexpected")
            if unexpected is None or unexpected > 0:
                errors.append(
                    f"static_analysis: `{name}` compiled "
                    f"{rep.get('cache_growth')!r} time(s) in the open-loop "
                    f"region (allowed {rep.get('allowed')!r})"
                )
        kv = sa.get("kernel_vmem_bytes")
        if not isinstance(kv, dict) or not kv:
            errors.append("static_analysis.kernel_vmem_bytes missing")

    # v7: crash-safety evidence — warm-restart parity, checksum
    # fallback, preemption round-trips
    rec = doc.get("recovery", {})
    if not isinstance(rec, dict) or not rec:
        errors.append("recovery block missing")
    else:
        for k in ("snapshot_us", "restore_us"):
            v = rec.get(k)
            if not isinstance(v, (int, float)) or not v > 0:
                errors.append(
                    f"recovery.{k} not a positive number: {v!r}"
                )
        if not isinstance(rec.get("snapshots_written"), int) or (
            rec.get("snapshots_written", 0) < 2
        ):
            errors.append(
                f"recovery.snapshots_written "
                f"{rec.get('snapshots_written')!r} < 2 — the rotation "
                "path was not exercised"
            )
        if rec.get("resume_parity") is not True:
            errors.append(
                "recovery.resume_parity is not true — a warm-restarted "
                "engine diverged from the uninterrupted oracle"
            )
        fb, inj = rec.get("checkpoint_fallbacks"), rec.get(
            "injected_corruptions"
        )
        if fb != inj:
            errors.append(
                f"recovery.checkpoint_fallbacks {fb!r} != injected "
                f"corruptions {inj!r} — a corrupt snapshot was either "
                "missed by the checksum or double-counted"
            )
        pre = rec.get("preemptions")
        if not isinstance(pre, int) or pre < 1:
            errors.append(
                f"recovery.preemptions {pre!r} < 1 — the urgent arrival "
                "did not preempt a resident slot"
            )
        if not isinstance(rec.get("preempt_resumes"), int) or (
            rec.get("preempt_resumes", 0) < 1
        ):
            errors.append(
                f"recovery.preempt_resumes "
                f"{rec.get('preempt_resumes')!r} < 1 — a parked window "
                "was never restored"
            )
        if rec.get("preempt_parity") is not True:
            errors.append(
                "recovery.preempt_parity is not true — a parked/"
                "restored window diverged from the oracle"
            )
        if isinstance(pre, int) and pre > 0:
            for k in (
                "preempt_park_us",
                "preempt_restore_us",
                "preempt_round_trip_us",
            ):
                v = rec.get(k)
                if not isinstance(v, (int, float)) or not v > 0:
                    errors.append(
                        f"recovery.{k} not a positive number: {v!r}"
                    )

    # sidecar artifacts exist and are structurally sound
    arts = doc.get("artifacts", {})
    base = Path(path).resolve().parent
    trace_name = arts.get("trace")
    if not isinstance(trace_name, str):
        errors.append("artifacts.trace missing")
    else:
        errors.extend(_validate_trace_file(base / trace_name))
    metrics_name = arts.get("metrics")
    if not isinstance(metrics_name, str):
        errors.append("artifacts.metrics missing")
    else:
        try:
            msnap = json.loads((base / metrics_name).read_text())
            missing = [k for k in HIST_KEYS if k not in msnap]
            if missing:
                errors.append(
                    f"metrics snapshot {metrics_name} missing {missing}"
                )
            if "engine.slo.status" not in msnap:
                errors.append(
                    f"metrics snapshot {metrics_name} missing the "
                    f"engine.slo.status gauge"
                )
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"metrics snapshot unreadable: {e}")
    ts_name = arts.get("timeseries")
    if not isinstance(ts_name, str):
        errors.append("artifacts.timeseries missing")
    else:
        errors.extend(
            _validate_timeseries_file(base / ts_name, n_samples, cons)
        )
    return errors


def _validate_timeseries_file(
    path: Path, n_samples, cons: Dict
) -> List[str]:
    """The JSONL sidecar must parse, carry one object per sample, and
    its per-line deltas must re-sum to the doc's consistency totals
    (ring never overflowed in a bench run, so the file is complete)."""
    try:
        lines = Path(path).read_text().splitlines()
    except OSError as e:
        return [f"timeseries sidecar unreadable: {e}"]
    errors: List[str] = []
    if isinstance(n_samples, int) and len(lines) != n_samples:
        errors.append(
            f"timeseries sidecar has {len(lines)} lines, doc says "
            f"{n_samples} samples"
        )
    sums: Dict[str, float] = {}
    for i, line in enumerate(lines):
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as e:
            return errors + [f"timeseries sidecar line {i + 1}: {e}"]
        for want in ("t", "dt", "values", "deltas"):
            if want not in obj:
                errors.append(
                    f"timeseries sidecar line {i + 1} missing {want!r}"
                )
        for k, v in obj.get("deltas", {}).items():
            sums[k] = sums.get(k, 0.0) + v
    for k, c in cons.items():
        if not isinstance(c, dict):
            continue
        st = c.get("series_total")
        if isinstance(st, (int, float)) and abs(
            sums.get(k, 0.0) - st
        ) > 1e-6 * max(abs(st), 1.0):
            errors.append(
                f"timeseries sidecar deltas for {k} sum to "
                f"{sums.get(k, 0.0)!r}, doc consistency says {st!r}"
            )
    return errors


def _validate_trace_file(path: Path) -> List[str]:
    """The exported Chrome trace must be loadable and carry both span
    families (request-lifecycle and tick-phase)."""
    try:
        trace = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"trace artifact unreadable: {e}"]
    evs = trace.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        return ["trace artifact has no traceEvents"]
    errors = []
    spans = [e for e in evs if e.get("ph") == "X"]
    if not spans:
        errors.append("trace artifact has no complete ('X') spans")
    if not any(e.get("ph") == "M" for e in evs):
        errors.append("trace artifact has no thread metadata")
    names = {e.get("name") for e in spans}
    for needed in ("occupy", "engine.tick.dispatch", "queue"):
        if needed not in names:
            errors.append(f"trace artifact missing {needed!r} spans")
    bad = [
        e for e in spans
        if not isinstance(e.get("ts"), (int, float))
        or not isinstance(e.get("dur"), (int, float))
        or e["dur"] < 0
    ]
    if bad:
        errors.append(f"trace artifact has {len(bad)} malformed spans")
    return errors


def run() -> None:
    main([])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale 4096-512-2 (slow on CPU)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--quick", action="store_true",
                    help="open-loop async serving bench -> stream_bench.json"
                         " (combine with --full for the longer trace)")
    ap.add_argument("--json", type=Path, default=None,
                    help="output path for --quick (default repo root)")
    ap.add_argument("--validate", type=Path, default=None,
                    help="validate an existing stream_bench.json and exit")
    args = ap.parse_args(argv)
    if args.validate is not None:
        errors = validate(args.validate)
        if errors:
            for e in errors:
                print(f"stream_bench.json INVALID: {e}", file=sys.stderr)
            return 1
        print(f"{args.validate}: OK")
        return 0
    if args.quick:
        open_loop_run(quick=not args.full, json_path=args.json)
        return 0

    sizes = (4096, 512, 2) if args.full else (1024, 256, 2)
    cfg = snn.SNNConfig(layer_sizes=sizes, num_steps=25)
    params = snn.init_params(jax.random.PRNGKey(0), cfg)
    wq = quant.quantize(params["layer0"]["w"])  # for the kernel comparison
    B, T, K = args.batch, cfg.num_steps, sizes[0]
    rng = np.random.default_rng(0)

    dense_fwd = jax.jit(lambda s: snn.forward(params, s, cfg, train=False))
    event_fwd = jax.jit(lambda s: runtime.event_forward(params, s, cfg))
    dense_ops = energy.snn_inference_ops(
        sizes, T, [1.0] * cfg.num_layers, event_driven=False
    )

    print(f"# layer_sizes={sizes} T={T} B={B} (per-inference numbers)")
    print("rate,meas_events_l0,aer_adds,dense_adds,add_ratio,"
          "aer_energy_pj,dense_energy_pj,energy_ratio,"
          "dense_fwd_us,event_fwd_us,events_per_sec,"
          "spike_mm_us,aer_mm_us")
    for rate in RATES:
        spikes = (rng.random((T, B, K)) < rate).astype(np.float32)
        spikes_j = jnp.asarray(spikes)

        _, _, ev = event_fwd(spikes_j)
        ev_mean = np.asarray(ev).mean(axis=1)  # per-inference events/layer
        oc = energy.snn_ops_from_events(sizes, T, ev_mean)
        aer_adds = oc.ops.get("add_i32", 0.0)
        dense_adds = dense_ops.ops["add_i32"]

        t_dense = time_fn(dense_fwd, spikes_j, warmup=1, iters=3)
        t_event = time_fn(event_fwd, spikes_j, warmup=1, iters=3)
        ev_total = float(np.asarray(ev).sum())
        evps = ev_total / args.batch / (t_event * 1e-6) if t_event else 0.0

        # kernel-level: one step's integration, dense vs AER event list
        row = jnp.asarray(spikes[0, 0][None, :].astype(np.int8))
        t_mm = time_fn(ops.spike_matmul, row, wq, warmup=1, iters=3)
        idx = np.nonzero(spikes[0, 0])[0]
        cap = max(int(K * max(rate, 0.01)) + 8, 8)
        a = np.zeros(cap, np.int32)
        v = np.zeros(cap, np.int32)
        a[: len(idx[:cap])] = idx[:cap]
        v[: len(idx[:cap])] = 1
        t_aer = time_fn(
            ops.aer_spike_matmul, jnp.asarray(a), jnp.asarray(v), wq,
            warmup=1, iters=3,
        )

        print(
            f"{rate:.2f},{ev_mean[0]:.0f},{aer_adds:.3g},{dense_adds:.3g},"
            f"{aer_adds/dense_adds:.3f},"
            f"{oc.energy_pj():.3g},{dense_ops.energy_pj():.3g},"
            f"{oc.energy_pj()/dense_ops.energy_pj():.3f},"
            f"{t_dense:.0f},{t_event:.0f},{evps:.0f},"
            f"{t_mm:.0f},{t_aer:.0f}",
            flush=True,
        )


if __name__ == "__main__":
    raise SystemExit(main())
