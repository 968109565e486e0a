"""Serving launcher: batched LM generation or streaming SNN inference.

LM zoo (token decode, continuous batching over prompts):
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m \
      --reduced --requests 8 --new-tokens 16 [--quant q115]

SNN streaming (event-driven, persistent membrane state, measured energy;
async admission with open-loop Poisson arrivals, deadlines, priorities):
  PYTHONPATH=src python -m repro.launch.serve --snn --requests 16 \
      --batch 4 --chunk-steps 5 --image-hw 32 [--dvs] \
      [--arrival-rate 20] [--deadline-ms 500] \
      [--max-queue 8] [--shed] [--drain-timeout 60] \
      [--inject-faults 4 --fault-seed 0] \
      [--snapshot-dir /tmp/snn-snap --snapshot-every 5 --restore] \
      [--preempt] \
      [--metrics-json metrics.json] [--trace-out trace.json] \
      [--profile-ticks 20 --profile-dir /tmp/snn-profile]

Crash safety (with --snn): ``--snapshot-dir D --snapshot-every S``
writes a rotating atomic engine snapshot every S seconds (resident
membranes, AER rings, queue, parked + preempt-parked requests);
``--restore`` warm-restarts from the latest intact one — in-flight
windows resume mid-window, bit-exactly, and checksum-corrupt snapshots
fall back to the previous save.  ``--preempt`` enables deadline-aware
slot preemption (see ``SNNStreamEngine(preempt=True)``).

Fault tolerance (with --snn): ``--max-queue N`` bounds the admission
queue (overflow sheds priority-0 requests, parks higher priorities) and
``--shed`` turns on the EDF feasibility shedder — both via
``repro.faults.AdmissionPolicy``.  ``--drain-timeout S`` bounds the
closed-loop drain and prints the per-slot stuck diagnostic on expiry
instead of hanging.  ``--inject-faults N`` runs the request load under a
seeded chaos schedule (NaN membranes, corrupted rings, transient chunk
exceptions) from ``repro.faults.inject`` — faulted requests come back
``disposition="quarantined"`` while the other slots keep serving, and
the summary prints the fault-plane counters plus ``engine.health()``'s
diagnosis verdict.

Observability (with --snn): ``--metrics-json`` dumps the engine's full
instrument snapshot; ``--trace-out`` turns the engine's span ring on
(it is off by default) and writes it as Perfetto-loadable Chrome trace
JSON: the request lifecycle (``submit``, ``queue``, ``engine.admit``,
``occupy`` on the slot's track, ``complete``) and the serving thread's
phases (``engine.poll``, ``engine.submit``, ``engine.admit.upload`` /
``.dispatch``, ``engine.tick.prep``, ``engine.tick.dispatch``,
``engine.retire`` / ``.fetch``, ``engine.finalize``, ``engine.sample``).
``--timeseries-out`` writes the per-tick time series as JSONL, and
``--profile-ticks N`` wraps N steady-state ticks in a programmatic
``jax.profiler`` capture, which shows the same engine phases as host
annotations against the device's operations.  Both
open- and closed-loop modes report the trailing-window miss-rate /
events/s / ticks/s and the SLO burn-rate verdict
(healthy/degraded/breach) from ``engine.health()``.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

import repro.configs as configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serving.engine import Request, ServeEngine


def _serve_lm(args) -> None:
    cfg = configs.get(args.arch).reduced()
    if args.quant:
        cfg = dataclasses.replace(cfg, quant=args.quant)
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(
        model, params, batch_size=args.batch, cache_len=args.cache_len
    )
    rng = np.random.default_rng(0)

    def prompt():
        L = int(rng.integers(4, 24))
        if cfg.num_codebooks:
            return rng.integers(0, cfg.vocab_size, (L, cfg.num_codebooks)).astype(np.int32)
        return rng.integers(0, cfg.vocab_size, L).astype(np.int32)

    reqs = [
        Request(prompt=prompt(), max_new_tokens=args.new_tokens,
                temperature=args.temperature)
        for _ in range(args.requests)
    ]
    t0 = time.time()
    outs = engine.generate(reqs)
    dt = time.time() - t0
    n = sum(len(o) for o in outs)
    print(f"{args.arch}: served {len(reqs)} reqs / {n} tokens in {dt:.2f}s "
          f"({n/dt:.1f} tok/s on {jax.devices()[0].device_kind}, "
          f"quant={cfg.quant})")


def _serve_snn(args) -> None:
    import jax.numpy as jnp

    from repro.core import snn
    from repro.events import aer
    from repro.serving.snn_engine import SNNStreamEngine, StreamRequest

    if args.requests <= 0:
        print("snn: nothing to serve (--requests 0)")
        return
    hw = args.image_hw
    # polarity-aware input layer: DVS ON/OFF events get their own input
    # channels (or signed weights); frame-camera mode keeps hw*hw inputs
    input_size = (
        aer.input_size_for(hw * hw, args.polarity) if args.dvs else hw * hw
    )
    cfg = snn.SNNConfig(
        layer_sizes=(input_size, args.hidden, 2), num_steps=args.num_steps
    )
    params = snn.init_params(jax.random.PRNGKey(0), cfg)
    # SLOs: the latency target follows the requested deadline budget
    # (default 1 s without one); the deadline-miss error budget is 5%
    from repro.obs import default_slos

    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms > 0 else None

    # fault-tolerance plane (all opt-in, default off: unbounded queue,
    # no shedding, no chaos)
    admission = None
    if args.max_queue > 0 or args.shed:
        from repro.faults import AdmissionPolicy

        admission = AdmissionPolicy(
            max_queue_depth=args.max_queue if args.max_queue > 0 else None,
            shed_unmeetable=args.shed,
        )
    injector = None
    if args.inject_faults > 0:
        from repro.faults import FaultInjector, FaultSchedule

        chunks = -(-cfg.num_steps // args.chunk_steps)
        horizon = max(
            2 * args.requests * chunks // max(args.batch, 1), 8
        )
        injector = FaultInjector(FaultSchedule.generate(
            args.fault_seed, args.inject_faults, ticks=horizon,
            num_slots=args.batch, num_layers=cfg.num_layers,
            kinds=("nan_membrane", "corrupt_ring", "chunk_exception"),
        ))

    engine = SNNStreamEngine(
        params, cfg, num_slots=args.batch, chunk_steps=args.chunk_steps,
        seed=1, backend=args.snn_backend,
        pipeline_depth=0 if args.no_pipeline else 1,
        slos=default_slos(p99_target_s=deadline_s or 1.0),
        admission=admission, injector=injector,
        preempt=args.preempt,
        trace_capacity=8192 if args.trace_out else 0,
    )

    # crash safety: warm-restart from the latest intact snapshot under
    # --snapshot-dir (corrupt/partial ones are skipped with a warning),
    # then keep snapshotting on the --snapshot-every cadence below
    if args.restore:
        if not args.snapshot_dir:
            raise SystemExit("--restore requires --snapshot-dir")
        restored = engine.restore_latest_snapshot(args.snapshot_dir)
        if restored is not None:
            print(f"snn: warm-restarted from {restored} "
                  f"(resident slots resume mid-window)")
        else:
            print(f"snn: no usable snapshot under {args.snapshot_dir}; "
                  f"cold start")
    snap_state = {"t": time.perf_counter()}

    def _maybe_snapshot():
        if not args.snapshot_dir or args.snapshot_every <= 0:
            return
        if time.perf_counter() - snap_state["t"] >= args.snapshot_every:
            engine.snapshot_auto(args.snapshot_dir)
            snap_state["t"] = time.perf_counter()

    key = jax.random.PRNGKey(2)
    reqs = []
    if args.dvs:
        # DVS event-camera input: densify each synthetic recording into
        # polarity-aware input planes behind the EventStream interface
        stream, labels = aer.dvs_collision_batch(
            key, args.requests, image_hw=hw, num_steps=cfg.num_steps,
            capacity=8 * hw * hw,
        )
        planes = aer.input_planes(
            stream, cfg.num_steps, hw * hw, polarity_mode=args.polarity
        )
        for i in range(args.requests):
            reqs.append(StreamRequest(spikes=np.asarray(planes[:, i])))
    else:
        from repro.data import collision

        data_cfg = collision.CollisionConfig(
            image_hw=hw, num_train=0, num_test=args.requests
        )
        _, _, test_x, _ = collision.generate(data_cfg)
        for x in test_x:
            reqs.append(StreamRequest(image=x.reshape(-1)))

    if deadline_s is not None:
        reqs = [dataclasses.replace(r, deadline_s=deadline_s) for r in reqs]

    profile = None
    if args.profile_ticks > 0:
        from repro.obs import profile_ticks

        profile = profile_ticks(
            engine, args.profile_dir, num_ticks=args.profile_ticks
        )

    t0 = time.time()
    if args.arrival_rate > 0:
        # open-loop: Poisson arrivals at the requested rate, submitted to
        # the async engine while earlier requests' chunks are in flight
        gaps = np.random.default_rng(3).exponential(
            1.0 / args.arrival_rate, len(reqs)
        )
        arrivals = np.cumsum(gaps)
        results, i = [], 0
        start = time.perf_counter()
        while i < len(reqs) or not engine.idle():
            now = time.perf_counter() - start
            while i < len(reqs) and arrivals[i] <= now:
                engine.submit(reqs[i])
                i += 1
            if engine.idle() and i < len(reqs):
                time.sleep(
                    max(arrivals[i] - (time.perf_counter() - start), 0.0)
                )
                continue
            results.extend(engine.poll())
            _maybe_snapshot()
        results.sort(key=lambda r: r.request_id)
    elif args.snapshot_dir and args.snapshot_every > 0:
        # closed-loop with a live snapshot cadence: poll manually so the
        # engine can checkpoint between ticks (drain() would block)
        for r in reqs:
            engine.submit(r)
        results, t_start = [], time.perf_counter()
        while not engine.idle():
            if (args.drain_timeout > 0
                    and time.perf_counter() - t_start > args.drain_timeout):
                print(f"snn: STALLED after {args.drain_timeout:.1f}s — "
                      f"stuck slots: {engine.stall_snapshot()['slots']}")
                break
            results.extend(engine.poll())
            _maybe_snapshot()
    elif args.drain_timeout > 0:
        # bounded closed-loop drain: a wedged tick loop surfaces as the
        # per-slot stuck diagnostic instead of hanging the launcher
        from repro.serving.snn_engine import EngineStallError

        for r in reqs:
            engine.submit(r)
        try:
            results = engine.drain(timeout_s=args.drain_timeout)
        except EngineStallError as e:
            print(f"snn: STALLED after {args.drain_timeout:.1f}s — "
                  f"stuck slots: {e.snapshot['slots']}")
            results = list(e.results)
    else:
        results = engine.run(reqs)
    dt = time.time() - t0
    if profile is not None:
        profile.stop()
    # latency / energy / throughput aggregate over *served* requests
    # only — shed requests never ran and quarantined ones carry no
    # trustworthy outputs (their fault code is the result)
    ok = [r for r in results if r.disposition == "ok"]
    n_shed = sum(r.disposition == "shed" for r in results)
    n_quar = sum(r.disposition == "quarantined" for r in results)
    rate = np.array([r.spike_rate for r in ok]) if ok else np.zeros(1)
    events_total = float(sum(r.events_per_layer.sum() for r in ok))
    src = f"dvs-events/{args.polarity}" if args.dvs else "rate-coded"
    loop = (
        f"open-loop {args.arrival_rate:.0f} req/s"
        if args.arrival_rate > 0
        else "closed-loop"
    )
    disp = (
        f" (ok {len(ok)} | shed {n_shed} | quarantined {n_quar})"
        if (n_shed or n_quar) else ""
    )
    print(
        f"snn[{input_size}->{args.hidden}->2, T={cfg.num_steps}, {src}]: "
        f"served {len(results)} reqs in {dt:.2f}s on {args.batch} slots "
        f"({loop}){disp}"
    )
    # report from the metrics snapshot: the engine-lifetime request
    # histograms and counters span every episode an open-loop trace with
    # arrival gaps crosses, so both modes read the same instruments
    snap = engine.metrics_snapshot()
    lat, qw, en = (
        snap["engine.request.latency_s"],
        snap["engine.request.queue_wait_s"],
        snap["engine.request.energy_pj"],
    )
    misses = int(snap["engine.requests.deadline_missed"]["value"])
    served = int(snap["engine.requests.completed"]["value"])
    print(
        f"  latency p50/p99: {lat['p50']*1e3:.1f}/{lat['p99']*1e3:.1f} ms"
        f" | queue wait p50: {qw['p50']*1e3:.1f} ms | "
        f"throughput: {events_total/max(dt, 1e-9):.0f} events/s | "
        f"input rate: {rate.mean():.3f}"
    )
    budget = (
        f"{args.deadline_ms:.0f} ms" if deadline_s is not None else "none"
    )
    print(
        f"  deadline budget {budget}: missed {misses}/{served} "
        f"({misses/max(served, 1):.1%})"
    )
    # windowed signals + SLO verdict: the evolving view (trailing-window
    # counter deltas from the per-tick time series), not lifetime means,
    # plus the multi-window burn-rate judgement over the same series
    health = engine.health()
    ts = engine.timeseries
    win_s = 1.0
    print(
        f"  windowed ({win_s:.0f}s): miss-rate "
        f"{engine.windowed_miss_rate(win_s):.1%} | "
        f"{ts.rate('engine.episode.events', win_s):.0f} events/s | "
        f"{ts.rate('engine.tick.dispatch_s.count', win_s):.1f} ticks/s "
        f"({len(ts)} samples over {ts.span_s():.2f}s)"
    )
    fired = [
        f"{s['name']}:{s['status']}"
        for s in health["slos"] if s["status"] != "healthy"
    ]
    print(
        f"  health: {health['status'].upper()}"
        + (f" ({', '.join(fired)})" if fired else "")
        + f" — {len(health['slos'])} SLOs, burn-rate rules over "
        f"{health['span_s']:.2f}s of samples"
    )
    diag = health["diagnosis"]
    print(f"  diagnosis: {diag['verdict'].upper()} — {diag['hint']}")
    if admission is not None or injector is not None or n_shed or n_quar:
        print(
            f"  fault plane: shed {n_shed} "
            f"({engine.shed_rate():.1%} of submitted) | parked served "
            f"{int(sum(r.parked for r in ok))} | quarantined {n_quar} | "
            f"injected "
            f"{int(snap['engine.faults.injected']['value'])} | retries "
            f"{int(snap['engine.faults.chunk_retries']['value'])} | "
            f"demotions "
            f"{int(snap['engine.faults.backend_demoted']['value'])}"
        )
    print(
        f"  measured energy/inference: mean {en['mean']/1e3:.1f} nJ, "
        f"p99 {en['p99']/1e3:.1f} nJ (model estimate from counted events)"
    )
    tb = engine.tick_breakdown()
    print(
        f"  tick breakdown (pipeline_depth={tb['pipeline_depth']}, "
        f"{tb['ticks']} ticks): host prep {tb['host_prep_us']:.0f} us | "
        f"dispatch {tb['dispatch_us']:.0f} us "
        f"(p99 {tb['dispatch_p99_us']:.0f} us) | "
        f"stats fetch {tb['stats_fetch_us']:.0f} us "
        f"(spike trains stay device-resident; the fetch is the tick's "
        f"only host transfer)"
    )
    if args.metrics_json:
        engine.metrics.write_json(args.metrics_json)
        print(f"  metrics snapshot -> {args.metrics_json}")
    if args.trace_out:
        engine.export_trace(args.trace_out)
        print(
            f"  chrome trace ({len(engine.trace)} spans) -> "
            f"{args.trace_out} (load in ui.perfetto.dev)"
        )
    if args.timeseries_out:
        engine.timeseries.write_jsonl(args.timeseries_out)
        print(
            f"  time series ({len(engine.timeseries)} samples) -> "
            f"{args.timeseries_out}"
        )
    if profile is not None:
        if profile.error:
            raise SystemExit(f"jax.profiler capture FAILED: {profile.error}")
        print(
            f"  jax.profiler capture ({args.profile_ticks} "
            f"steady-state ticks) -> {args.profile_dir}"
        )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b",
                    choices=configs.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quant", default=None, choices=[None, "q115"])
    # streaming SNN mode
    ap.add_argument("--snn", action="store_true",
                    help="serve the event-driven SNN instead of an LM")
    ap.add_argument("--dvs", action="store_true",
                    help="synthetic DVS event-camera input (with --snn)")
    ap.add_argument("--polarity", default="two_channel",
                    choices=["two_channel", "signed", "on_only"],
                    help="DVS ON/OFF event mapping onto the input layer")
    ap.add_argument("--image-hw", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--num-steps", type=int, default=25)
    ap.add_argument("--chunk-steps", type=int, default=5)
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate in req/s "
                         "(0 = closed-loop batch, with --snn)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request latency budget in ms "
                         "(0 = no deadline, with --snn)")
    ap.add_argument("--snn-backend", default="auto",
                    choices=["auto", "jnp", "fused"],
                    help="chunk hot path: fused Pallas kernel, jnp "
                         "oracle, or auto (fused on TPU)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="synchronous ticks (disable the one-deep "
                         "stats-future pipeline; debugging aid)")
    # fault tolerance (with --snn)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the admission queue at N (overflow "
                         "sheds priority-0 requests, parks higher "
                         "priorities; 0 = unbounded)")
    ap.add_argument("--shed", action="store_true",
                    help="EDF feasibility shedding: reject requests "
                         "whose deadline is provably unmeetable at the "
                         "measured tick rate")
    ap.add_argument("--drain-timeout", type=float, default=0.0,
                    help="closed-loop drain timeout in seconds; on "
                         "expiry print the per-slot stuck diagnostic "
                         "instead of hanging (0 = wait forever)")
    ap.add_argument("--inject-faults", type=int, default=0,
                    help="chaos mode: inject N seeded faults (NaN "
                         "membranes, corrupted rings, transient chunk "
                         "exceptions) during the run")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for --inject-faults schedules")
    # crash safety / preemption (with --snn)
    ap.add_argument("--snapshot-dir", default=None,
                    help="directory for rotating engine snapshots "
                         "(atomic snap_* dirs, keep-3)")
    ap.add_argument("--snapshot-every", type=float, default=0.0,
                    help="snapshot cadence in seconds during the serve "
                         "loop (0 = never; requires --snapshot-dir)")
    ap.add_argument("--restore", action="store_true",
                    help="warm-restart from the latest intact snapshot "
                         "under --snapshot-dir before serving (corrupt "
                         "snapshots are skipped with a fallback)")
    ap.add_argument("--preempt", action="store_true",
                    help="deadline-aware slot preemption: a tighter-"
                         "deadline arrival with no free slot parks the "
                         "loosest resident window and resumes it later, "
                         "bit-exactly")
    # observability (with --snn)
    ap.add_argument("--metrics-json", default=None,
                    help="write the engine's metrics-registry snapshot "
                         "(counters/gauges/histograms) to this path")
    ap.add_argument("--trace-out", default=None,
                    help="record the engine's span ring (off otherwise) "
                         "and write it as Chrome trace-event JSON "
                         "(Perfetto-loadable)")
    ap.add_argument("--timeseries-out", default=None,
                    help="write the per-tick time series (counter "
                         "deltas, windowed rates) as JSONL")
    ap.add_argument("--profile-ticks", type=int, default=0,
                    help="capture a jax.profiler trace around N "
                         "steady-state ticks (0 = off)")
    ap.add_argument("--profile-dir", default="/tmp/snn-jax-profile",
                    help="output directory for --profile-ticks")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.snn:
        _serve_snn(args)
    else:
        _serve_lm(args)


if __name__ == "__main__":
    main()
