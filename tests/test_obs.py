"""Observability layer: histogram accuracy vs numpy (example + property
tests), NaN/inf quarantine, deterministic snapshot export, metrics
registry semantics, span lifecycle invariants on a live engine, Chrome
trace JSON round-trip, and the dispatch-attribution probe."""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import snn
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    TraceRecorder,
    dispatch_attribution,
    tick_instrumentation_cost_us,
)
from repro.obs.metrics import percentile_tolerance
from repro.serving.snn_engine import SNNStreamEngine, StreamRequest
from tests._hypothesis_compat import given, settings, st

CFG = snn.SNNConfig(layer_sizes=(64, 24, 2), num_steps=20)


def _params(seed=0):
    return snn.init_params(jax.random.PRNGKey(seed), CFG)


def _train(rate, seed, T=None):
    rng = np.random.default_rng(seed)
    T = T or CFG.num_steps
    return (rng.random((T, CFG.layer_sizes[0])) < rate).astype(np.float32)


# ------------------------------------------------------------ histograms
@pytest.mark.parametrize(
    "dist",
    ["lognormal", "uniform", "exponential"],
)
@pytest.mark.parametrize("q", [50, 90, 99])
def test_histogram_percentiles_vs_numpy(dist, q):
    """p50/p90/p99 within one log-bucket ratio of numpy on known
    distributions spanning several decades."""
    rng = np.random.default_rng(7)
    if dist == "lognormal":
        xs = rng.lognormal(mean=-5.0, sigma=1.5, size=20_000)
    elif dist == "uniform":
        xs = rng.uniform(1e-4, 1e-1, size=20_000)
    else:
        xs = rng.exponential(scale=3e-3, size=20_000)
    h = Histogram("t", lo=1e-7, hi=1e3, buckets_per_decade=16)
    for x in xs:
        h.record(x)
    est = h.percentile(q)
    true = float(np.percentile(xs, q))
    tol = percentile_tolerance(16) * 1.01  # one bucket ratio + epsilon
    assert true / tol <= est <= true * tol, (dist, q, est, true)


def test_histogram_exact_moments_and_accounting():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=0.0, sigma=2.0, size=5000)
    xs[0] = 1e-9  # underflow
    xs[1] = 1e9  # overflow
    h = Histogram("t", lo=1e-6, hi=1e6, buckets_per_decade=8)
    for x in xs:
        h.record(x)
    snap = h.snapshot()
    assert snap["count"] == len(xs)
    assert snap["sum"] == pytest.approx(xs.sum())
    assert snap["min"] == pytest.approx(xs.min())
    assert snap["max"] == pytest.approx(xs.max())
    # every recorded value is accounted for, exactly
    bucket_total = sum(c for _, c in snap["buckets"])
    assert (
        snap["underflow"] + snap["overflow"] + bucket_total
        == snap["count"]
    )
    assert snap["underflow"] >= 1 and snap["overflow"] >= 1
    # percentiles are monotone and clamped to observed range
    p = [h.percentile(q) for q in (1, 25, 50, 75, 90, 99, 100)]
    assert all(a <= b + 1e-12 for a, b in zip(p, p[1:]))
    assert snap["min"] <= p[0] and p[-1] <= snap["max"]


def test_histogram_nan_inf_quarantined():
    """Non-finite values land in the separate ``invalid`` tally and
    never touch count/sum/min/max/buckets — one diverged-loss NaN must
    not poison the mean forever or bisect into bucket 0."""
    h = Histogram("t", lo=1e-3, hi=1e3)
    h.record(1.0)
    h.record(float("nan"))
    h.record(float("inf"))
    h.record(float("-inf"))
    h.record(2.0)
    snap = h.snapshot()
    assert snap["invalid"] == 3
    assert snap["count"] == 2
    assert snap["sum"] == pytest.approx(3.0)
    assert snap["mean"] == pytest.approx(1.5)
    assert math.isfinite(snap["min"]) and math.isfinite(snap["max"])
    assert snap["underflow"] == 0  # NaN did not bisect into bucket 0
    assert sum(c for _, c in snap["buckets"]) == 2
    assert 1.0 <= h.percentile(50) <= 2.0
    h.reset()
    assert h.invalid == 0 and h.snapshot()["invalid"] == 0


def test_write_json_is_deterministic(tmp_path):
    """Two registries holding identical data but built in different
    insertion orders must serialize byte-identically (CI sidecars diff
    across runs)."""
    def build(order):
        reg = MetricsRegistry()
        for name in order:
            if name == "z.h":
                h = reg.histogram("z.h", lo=1e-3, hi=1e3)
            elif name == "a.c":
                reg.counter("a.c")
            else:
                reg.gauge("m.g")
        reg.counter("a.c").inc(3)
        reg.gauge("m.g").set(7)
        h = reg.get("z.h")
        for v in (0.01, 0.5, 12.0, 700.0):
            h.record(v)
        return reg

    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    build(["z.h", "a.c", "m.g"]).write_json(p1)
    build(["m.g", "a.c", "z.h"]).write_json(p2)
    b1, b2 = p1.read_bytes(), p2.read_bytes()
    assert b1 == b2
    doc = json.loads(b1)
    # nested keys are sorted too
    assert list(doc) == sorted(doc)
    assert list(doc["z.h"]) == sorted(doc["z.h"])


@settings(max_examples=60, deadline=None)
@given(
    xs=st.lists(
        st.floats(min_value=1e-3, max_value=1e3,
                  allow_nan=False, allow_infinity=False),
        min_size=1, max_size=400,
    ),
    q=st.integers(min_value=1, max_value=100),
)
def test_histogram_percentile_property(xs, q):
    """Property: for in-range value streams the estimated percentile
    stays within the documented one-log-bucket relative-error bound of
    the *exact* (nearest-rank) percentile numpy computes over the same
    values."""
    bpd = 16
    h = Histogram("t", lo=1e-4, hi=1e4, buckets_per_decade=bpd)
    for x in xs:
        h.record(x)
    est = h.percentile(q)
    # exact nearest-rank percentile (the definition the histogram
    # documents): the ceil(q/100 * n)-th smallest value
    xs_sorted = np.sort(np.asarray(xs))
    target = max(1, int(math.ceil(q / 100.0 * len(xs))))
    true = float(xs_sorted[target - 1])
    tol = percentile_tolerance(bpd) * (1 + 1e-9)
    assert true / tol <= est <= true * tol, (q, est, true)


def test_histogram_percentile_reset_mid_stream():
    """Percentiles after a reset reflect only post-reset values."""
    h = Histogram("t", lo=1e-3, hi=1e3, buckets_per_decade=16)
    for _ in range(100):
        h.record(100.0)
    h.reset()
    for _ in range(50):
        h.record(0.1)
    tol = percentile_tolerance(16) * (1 + 1e-9)
    for q in (50, 90, 99):
        est = h.percentile(q)
        assert 0.1 / tol <= est <= 0.1 * tol, (q, est)


def test_histogram_all_underflow_and_all_overflow():
    """Degenerate streams: everything below lo -> percentiles collapse
    to the observed min; everything above hi -> observed max."""
    h = Histogram("t", lo=1.0, hi=10.0)
    for v in (1e-4, 1e-3, 1e-2):
        h.record(v)
    assert h.snapshot()["underflow"] == 3
    for q in (1, 50, 99):
        assert h.percentile(q) == pytest.approx(1e-4)  # exact min
    h2 = Histogram("t2", lo=1.0, hi=10.0)
    for v in (100.0, 200.0, 300.0):
        h2.record(v)
    assert h2.snapshot()["overflow"] == 3
    for q in (1, 50, 99):
        assert h2.percentile(q) == pytest.approx(300.0)  # exact max


def test_histogram_empty_and_reset():
    h = Histogram("t", lo=1e-3, hi=1e3)
    assert h.percentile(50) == 0.0
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["p99"] == 0.0
    h.record(1.0)
    assert h.count == 1
    h.reset()
    assert h.count == 0 and h.sum == 0.0 and h.percentile(99) == 0.0


def test_counter_gauge_and_registry():
    reg = MetricsRegistry()
    c = reg.counter("a.b.c")
    c.inc()
    c.inc(2.5)
    assert reg.counter("a.b.c") is c and c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("a.b.g")
    g.set(7)
    assert g.value == 7.0
    with pytest.raises(TypeError):
        reg.gauge("a.b.c")  # kind mismatch is loud
    h = reg.histogram("x.h", lo=1e-3, hi=1e3)
    h.record(0.5)
    # prefix reset: only the a.b.* instruments zero
    reg.reset(prefix="a.b.")
    assert c.value == 0.0 and g.value == 0.0 and h.count == 1
    snap = reg.snapshot()
    assert set(snap) == {"a.b.c", "a.b.g", "x.h"}
    assert snap["a.b.c"]["type"] == "counter"
    assert snap["x.h"]["type"] == "histogram"
    json.dumps(snap)  # snapshot is JSON-able as-is


# ------------------------------------------------------------------ trace
def test_trace_ring_is_bounded_and_ordered():
    rec = TraceRecorder(capacity=8)
    for i in range(20):
        rec.span(f"s{i}", float(i), float(i) + 0.5, track="t")
    spans = rec.spans()
    assert len(spans) == 8  # oldest fell off the back
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]
    with pytest.raises(ValueError):
        rec.span("bad", 2.0, 1.0)  # t1 < t0 rejected
    rec.enabled = False
    rec.span("off", 0.0, 1.0)
    assert len(rec) == 8
    with pytest.raises(ValueError):
        TraceRecorder(capacity=-1)


@pytest.mark.parametrize("capacity", [0, 8])
def test_phase_times_its_block_and_fills_the_ring_when_on(capacity):
    """A phase gives its bounds to the caller either way; it lands in
    the ring under its own name only when the ring is on."""
    rec = TraceRecorder(capacity=capacity)
    with rec.phase("engine.poll", track="engine") as outer:
        with rec.phase("engine.tick.prep", track="tick",
                       args={"n": 1}) as inner:
            pass
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert inner.duration_s == inner.t1 - inner.t0 >= 0
    assert rec.enabled == (capacity > 0)
    spans = rec.spans()
    if capacity:
        # completed in exit order: the inner block first
        assert [(s.name, s.track) for s in spans] == [
            ("engine.tick.prep", "tick"), ("engine.poll", "engine")]
        assert (spans[0].t0, spans[0].t1) == (inner.t0, inner.t1)
        assert spans[0].args == {"n": 1}
    else:
        assert spans == []


def test_chrome_trace_round_trip(tmp_path):
    rec = TraceRecorder()
    rec.span("work", 1.0, 1.5, track="tick", args={"n": 3})
    rec.span("chunk", 1.1, 1.4, track="slot0", cat="request")
    rec.instant("done", 1.6, track="slot0")
    path = tmp_path / "trace.json"
    rec.write(path)
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    # metadata names the process and each track-thread
    names = {
        e["args"]["name"] for e in evs if e["name"] == "thread_name"
    }
    assert names == {"tick", "slot0"}
    assert any(e["name"] == "process_name" for e in evs)
    spans = [e for e in evs if e.get("ph") == "X"]
    inst = [e for e in evs if e.get("ph") == "i"]
    assert len(spans) == 2 and len(inst) == 1
    by_name = {e["name"]: e for e in spans}
    # timestamps shift to a common zero, microsecond units
    assert by_name["work"]["ts"] == pytest.approx(0.0)
    assert by_name["work"]["dur"] == pytest.approx(0.5e6)
    assert by_name["chunk"]["ts"] == pytest.approx(0.1e6)
    assert by_name["work"]["args"] == {"n": 3}
    # one pid, distinct tids per track
    assert by_name["work"]["tid"] != by_name["chunk"]["tid"]


# ------------------------------------------- engine lifecycle invariants
def test_engine_span_lifecycle_invariants():
    """Every completed request leaves a full span lifecycle in the ring:
    queue -> engine.admit -> occupy -> complete, with monotonic
    timestamps all ordered within the request."""
    params = _params()
    eng = SNNStreamEngine(
        params, CFG, num_slots=2, chunk_steps=6, trace_capacity=8192
    )
    n_req = 5
    rids = [
        eng.submit(StreamRequest(spikes=_train(0.3, s)))
        for s in range(n_req)
    ]
    eng.drain()
    spans = eng.trace.spans()
    assert all(
        s.t1 is None or s.t1 >= s.t0 for s in spans
    )  # monotonic within every span
    for rid in rids:
        mine = [
            s for s in spans if s.args and s.args.get("rid") == rid
        ]
        kinds = [s.name for s in mine]
        assert "submit" in kinds
        assert "queue" in kinds
        assert "engine.admit" in kinds
        assert "complete" in kinds
        assert kinds.count("occupy") == 1
        by = {s.name: s for s in mine}
        queue, stage = by["queue"], by["engine.admit"]
        occupy = by["occupy"]
        complete = by["complete"]
        # lifecycle ordering: submit == queue start <= queue end ==
        # admission start <= occupancy start (staging done) <=
        # admission end <= occupancy end == complete
        assert queue.t0 <= queue.t1 == stage.t0 <= stage.t1
        assert stage.t0 <= occupy.t0 <= stage.t1 <= occupy.t1
        assert occupy.t1 == complete.t0
        assert occupy.track == stage.track == complete.track
        assert queue.t0 == by["submit"].t0
        # completion args carry the result-facing fields
        assert complete.args["latency_ms"] > 0
        assert complete.args["energy_pj"] > 0
    # tick-phase spans exist on their own track
    assert any(
        s.track == "tick" and s.name == "engine.tick.dispatch" for s in spans
    )
    assert any(
        s.track == "tick" and s.name == "engine.tick.prep" for s in spans
    )


def test_default_engine_keeps_no_ring():
    """The span ring is off unless asked for; the tick histograms are
    fed from the phases all the same."""
    eng = SNNStreamEngine(_params(), CFG, num_slots=2, chunk_steps=6)
    eng.run([StreamRequest(spikes=_train(0.3, s)) for s in range(3)])
    assert not eng.trace.enabled and eng.trace.spans() == []
    tb = eng.tick_breakdown()
    assert tb["ticks"] > 0 and tb["host_prep_us"] > 0
    assert tb["dispatch_us"] > 0 and tb["stats_fetch_us"] > 0


def test_engine_metrics_snapshot_consistency():
    params = _params()
    eng = SNNStreamEngine(params, CFG, num_slots=2, chunk_steps=5)
    eng.run(
        [StreamRequest(spikes=_train(0.3, s), deadline_s=1e4)
         for s in range(4)]
        + [StreamRequest(spikes=_train(0.3, 9), deadline_s=0.0)]
    )
    snap = eng.metrics_snapshot()
    lat = snap["engine.request.latency_s"]
    assert lat["count"] == 5
    assert 0 < lat["p50"] <= lat["p90"] <= lat["p99"]
    assert snap["engine.request.queue_wait_s"]["count"] == 5
    assert snap["engine.request.energy_pj"]["count"] == 5
    assert snap["engine.requests.completed"]["value"] == 5
    assert snap["engine.requests.deadline_missed"]["value"] == 1
    assert snap["engine.episode.deadline_misses"]["value"] == 1
    # tick histograms agree with the derived breakdown
    tb = eng.tick_breakdown()
    disp = snap["engine.tick.dispatch_s"]
    assert tb["ticks"] == disp["count"] > 0
    assert tb["dispatch_us"] == pytest.approx(
        disp["sum"] / disp["count"] * 1e6
    )
    # per-request energy instrument sums to the results' total
    assert snap["engine.request.energy_pj"]["sum"] > 0


def test_wall_s_resets_per_episode():
    """Regression: wall_s was initialized in __init__ but never reset in
    _begin_episode, so a mid-episode events_per_sec() read could see the
    previous episode's denominator.  It now lives in the episode-scoped
    registry prefix and zeroes when a new episode opens."""
    eng = SNNStreamEngine(_params(), CFG, num_slots=1, chunk_steps=5)
    assert eng.wall_s == 0.0
    eng.run([StreamRequest(spikes=_train(0.4, 0))])
    first = eng.wall_s
    assert first > 0
    # next submit opens a fresh episode: the stale wall time is gone
    eng.submit(StreamRequest(spikes=_train(0.4, 1)))
    assert eng.wall_s == 0.0
    eng.poll()
    assert eng.wall_s == 0.0  # still open -> still no final wall time
    eng.drain()
    assert eng.wall_s > 0 and eng.wall_s is not first


# -------------------------------------------------------------- profiler
def test_dispatch_attribution_probe():
    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((256, 256))
    att = dispatch_attribution(f, x, warmup=1, iters=3)
    assert att["host_enqueue_us"] > 0
    assert att["device_wait_us"] >= 0
    assert att["total_us"] >= att["host_enqueue_us"]
    assert att["total_us"] == pytest.approx(
        att["host_enqueue_us"] + att["device_wait_us"]
    )
    assert 0.0 <= att["device_wait_frac"] <= 1.0
    assert "dominates" in att["verdict"]


def test_tick_instrumentation_cost_is_small():
    """The per-tick obs recording cost must be microseconds — far under
    the <2% tick budget stream_bench enforces against measured ticks."""
    us = tick_instrumentation_cost_us(num_slots=4, reps=500)
    assert 0 < us < 500  # generous CI-machine bound; typical is ~10us
