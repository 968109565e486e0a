"""Tick-phase profiling: ``jax.profiler`` capture + dispatch attribution.

Two instruments for ROADMAP item 2's open question — *where do the
~10.5ms of per-tick ``dispatch_us`` go?*

- ``profile_ticks(engine, ...)`` arms a programmatic
  ``jax.profiler.start_trace`` / ``stop_trace`` window around N
  steady-state engine ticks (skipping warmup polls so first-tick
  compilation never pollutes the capture).  The resulting directory
  opens in Perfetto / TensorBoard and shows device compute against the
  host tick loop.
- ``dispatch_attribution(fn, *args)`` is a dependency-free blocking
  probe: it times the chunk call *returning* (host enqueue — Python
  dispatch + graph launch) separately from ``block_until_ready``
  (device-compute wait), splitting the engine's ``dispatch_us`` bucket
  into "host overhead to attack" vs "the device was simply busy".  On
  backends that serialize dispatch behind donated buffers the enqueue
  share is the true host cost either way.

``tick_instrumentation_cost_us(...)`` microbenches the exact
metrics/trace operations one engine tick performs — its phase
annotations, histograms, counters and the per-tick time-series sample
the windowed-rate/SLO layer adds — against *scratch* instruments, so
``stream_bench.py`` can assert the observability layer costs <2% of a
tick without perturbing the live registry.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import TraceRecorder

__all__ = [
    "profile_ticks",
    "dispatch_attribution",
    "tick_instrumentation_cost_us",
]


class _TickProfileHandle:
    """Wraps ``engine.poll``: starts the jax profiler trace after
    ``skip`` polls, stops it ``num_ticks`` polls later, then restores
    the original ``poll``.  ``stop()`` is idempotent and safe to call
    early (e.g. the serve loop drained first)."""

    def __init__(self, engine, logdir: str, num_ticks: int, skip: int):
        self._engine = engine
        self.logdir = str(logdir)
        self.num_ticks = int(num_ticks)
        self._skip = int(skip)
        self._seen = 0
        self._started = False
        self.stopped = False
        self.error: Optional[str] = None
        self._orig_poll = engine.poll
        engine.poll = self._wrapped_poll  # instance attr shadows method

    def _start(self) -> None:
        try:
            jax.profiler.start_trace(self.logdir)
            self._started = True
        except Exception as e:  # profiler backend unavailable
            self.error = f"jax.profiler.start_trace failed: {e}"
            self.stopped = True
            self._engine.poll = self._orig_poll

    def _wrapped_poll(self):
        if not self._started and not self.stopped:
            if self._seen >= self._skip:
                self._start()
            else:
                self._seen += 1
        out = self._orig_poll()
        if self._started and not self.stopped:
            self._seen += 1
            if self._seen >= self._skip + self.num_ticks:
                self.stop()
        return out

    def stop(self) -> None:
        if self.stopped:
            return
        self.stopped = True
        self._engine.poll = self._orig_poll
        if self._started:
            # block so the capture includes the in-flight chunk's compute
            jax.block_until_ready(self._engine._states)
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                self.error = f"jax.profiler.stop_trace failed: {e}"


def profile_ticks(
    engine, logdir: str, num_ticks: int = 20, skip: int = 2
) -> _TickProfileHandle:
    """Arm a ``jax.profiler`` capture around the engine's next
    ``num_ticks`` steady-state polls (after ``skip`` warmup polls).

    Returns a handle; call ``handle.stop()`` after serving (idempotent —
    a no-op if the tick budget already closed the capture).  Works for
    both the open-loop ``poll()`` driver and the closed-loop ``run()``
    wrapper, which funnels through ``poll`` internally.
    """
    if num_ticks < 1:
        raise ValueError("num_ticks must be >= 1")
    return _TickProfileHandle(engine, logdir, num_ticks, max(0, skip))


def dispatch_attribution(
    fn, *args, warmup: int = 1, iters: int = 5
) -> Dict:
    """Split a jitted call's wall time into host-enqueue vs
    device-compute wait.

    Times ``fn(*args)`` *returning* (enqueue: Python/jit dispatch and
    graph launch) separately from ``jax.block_until_ready`` on its
    outputs (device wait).  Medians over ``iters``; each iteration
    blocks before the next so work never queues up.  The caller should
    pass a non-donating compiled function (``engine.chunk_for_timing()``)
    so the same arguments are reusable.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    enq, tot = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        enq.append(t1 - t0)
        tot.append(t2 - t0)
    enq.sort()
    tot.sort()
    enqueue_s = enq[len(enq) // 2]
    total_s = tot[len(tot) // 2]
    device_wait_s = max(total_s - enqueue_s, 0.0)
    frac = device_wait_s / total_s if total_s > 0 else 0.0
    if frac >= 0.5:
        verdict = (
            "device-compute wait dominates: dispatch_us is the chunk's "
            "actual compute, not host dispatch overhead to attack"
        )
    else:
        verdict = (
            "host enqueue dominates: dispatch_us is Python/jit graph "
            "launch overhead — attack the host path"
        )
    return {
        "host_enqueue_us": enqueue_s * 1e6,
        "device_wait_us": device_wait_s * 1e6,
        "total_us": total_s * 1e6,
        "device_wait_frac": frac,
        "iters": iters,
        "verdict": verdict,
    }


def tick_instrumentation_cost_us(
    num_slots: int, reps: int = 2000
) -> float:
    """Measured cost (µs) of the metrics/trace work one engine tick
    performs, against scratch instruments: the phases of a poll that
    dispatches one chunk and retires one (``engine.poll``,
    ``engine.tick.prep``, ``engine.tick.dispatch``, ``engine.retire``,
    ``engine.retire.fetch``, ``engine.sample``) on a recorder with the
    engine's default ring (off), 3 tick-phase histogram records, the
    counter/gauge updates ``_tick``/``_retire`` make (events and steps
    per slot), and one time-series sample (with latency-bucket
    tracking) as taken by the windowed-rate/SLO layer each ``poll()``.
    Per-request work (admission, finalize) is left out.  This is the
    number ``stream_bench.py`` compares against the measured tick time
    to bound instrumentation overhead."""
    from repro.obs.timeseries import TimeSeriesSampler

    reg = MetricsRegistry()
    rec = TraceRecorder(capacity=0)
    hs = [
        reg.histogram(f"probe.tick.{k}_s", lo=1e-7, hi=10.0)
        for k in ("host_prep", "dispatch", "stats_fetch")
    ]
    lat = reg.histogram("probe.request.latency_s", lo=1e-6, hi=1e3)
    lat.record(0.05)
    ticks = reg.counter("probe.ticks")
    events = reg.counter("probe.events")
    steps = reg.counter("probe.steps")
    depth = reg.gauge("probe.queue_depth")
    sampler = TimeSeriesSampler(
        reg, capacity=4096, track_buckets=("probe.request.latency_s",)
    )
    t_start = time.perf_counter()
    phase = rec.phase
    for i in range(reps):
        with phase("engine.poll", track="engine"):
            with phase("engine.tick.prep", track="tick") as prep:
                pass
            with phase("engine.tick.dispatch", track="tick") as disp:
                pass
            t2 = time.perf_counter()
            with phase("engine.retire", track="tick"):
                with phase("engine.retire.fetch", track="tick"):
                    pass
                for _ in range(num_slots):
                    events.inc(1234.0)
                    steps.inc(5.0)
            hs[0].record(prep.duration_s)
            hs[1].record(disp.duration_s)
            hs[2].record(time.perf_counter() - t2)
            ticks.inc()
            depth.set(float(i % 7))
            lat.record(0.01 * (1 + i % 3))
            with phase("engine.sample", track="engine"):
                sampler.sample()
    return (time.perf_counter() - t_start) / reps * 1e6
