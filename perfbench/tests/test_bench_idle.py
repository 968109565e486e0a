"""The engine's phases reach the profiler's trace, and ``tools/idle.py``
names each device idle gap by the innermost host span holding it."""

import copy
import json
import pathlib

import numpy as np
import pytest

from perfbench import trace
from perfbench.tools import idle

DATA = pathlib.Path(__file__).resolve().parent / "data"

PHASES = {
    # phase: the phases it may sit directly inside
    "engine.poll": {None},
    "engine.submit": {None},
    "engine.admit": {"engine.poll"},
    "engine.admit.upload": {"engine.admit"},
    "engine.admit.dispatch": {"engine.admit"},
    "engine.tick.prep": {"engine.poll"},
    "engine.tick.dispatch": {"engine.poll"},
    "engine.retire": {"engine.poll"},
    "engine.retire.fetch": {"engine.retire"},
    "engine.finalize": {"engine.poll"},
    "engine.sample": {"engine.poll", "engine.submit"},
}


def _recorded():
    return json.loads((DATA / "trace_c64_frames_max.json").read_text())


def test_engine_phases_reach_a_profiler_capture(tmp_path):
    import jax

    from repro.core import snn
    from repro.serving.snn_engine import SNNStreamEngine, StreamRequest

    cfg = snn.SNNConfig(layer_sizes=(32, 16, 2), num_steps=8)
    eng = SNNStreamEngine(snn.init_params(jax.random.PRNGKey(0), cfg), cfg,
                          num_slots=2, chunk_steps=4)
    rng = np.random.default_rng(0)

    def train():
        return (rng.random((8, 32)) < 0.3).astype(np.float32)

    eng.run([StreamRequest(spikes=train()) for _ in range(3)])  # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run([StreamRequest(spikes=train()) for _ in range(3)])
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    host = idle.extract(str(path))["host"]
    assert host and all(d >= 0 for _, _, d in host)
    spans = idle._nest(host)
    seen = {}
    for name, s, e, parent, _ in spans:
        outer = None if parent is None else spans[parent][0]
        assert outer in PHASES[name], (name, outer)
        assert parent is None or spans[parent][1] <= s <= e <= spans[parent][2]
        seen[name] = seen.get(name, 0) + 1
    assert set(seen) == set(PHASES)
    assert seen["engine.submit"] == 3 and seen["engine.finalize"] == 3
    assert seen["engine.admit"] == seen["engine.admit.upload"] == 3


def test_without_engine_spans_gaps_keep_their_names():
    compact = _recorded()
    r = trace.reduce(compact, kernels=("snn_chunk",))
    a = idle.attribute(compact)
    assert a["idle_gaps"] == r["idle_gaps"]
    assert a["host_spans"] == {}
    assert sum(a["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)


def test_engine_spans_name_the_gap_they_hold():
    """The longest gap of the recorded slice lies in a ``bench.poll``;
    with engine spans around it, it takes the innermost one's name, and
    the other readings of the trace do not move."""
    compact = _recorded()
    before = trace.reduce(compact, kernels=("snn_chunk",))
    plain = idle.attribute(compact)
    # the gap's bounds, from the device's busy intervals
    lo, hi = idle._window(compact, compact["devices"][0]["ops"])
    s, e = max(idle._gaps(compact["devices"][0]["ops"], lo, hi),
               key=lambda g: g[1] - g[0])
    assert (e - s) * 1e-9 == pytest.approx(plain["idle_gaps"][0][1])
    poll = next(h for h in compact["host"] if h[0] == "bench.poll"
                and h[1] <= s and h[1] + h[2] >= e)
    ps, pe = poll[1], poll[1] + poll[2]
    synthetic = [
        ["engine.poll", ps + 1, pe - ps - 2],
        ["engine.retire", s - 20, e - s + 30],
        ["engine.retire.fetch", s - 10, e - s + 20],
        ["engine.sample", pe - 1000, 500],
    ]
    with_engine = copy.deepcopy(compact)
    with_engine["host"] = sorted(compact["host"] + synthetic,
                                 key=lambda h: h[1])
    after = trace.reduce(with_engine, kernels=("snn_chunk",))
    for k in ("window_s", "busy_s", "programs", "kernels", "device_ops"):
        assert after[k] == before[k]
    a = idle.attribute(with_engine)
    assert a["idle_gaps"][0] == ["engine.retire.fetch",
                                 pytest.approx((e - s) * 1e-9)]
    assert a["idle_by_span"]["engine.retire.fetch"] == pytest.approx(
        (e - s) * 1e-9)
    assert sum(a["idle_by_span"].values()) == pytest.approx(
        sum(plain["idle_by_span"].values()))
    assert a["host_spans"]["engine.retire.fetch"] == {
        "time_s": pytest.approx((e - s + 20) * 1e-9), "count": 1.0}
    assert set(a["host_spans"]) == {s[0] for s in synthetic}


def _compact(host):
    return {
        "devices": [{"name": "/device:TPU:0", "modules": [],
                     "ops": [["a", 0, 10], ["b", 100, 10]]}],
        "host": [["bench.traced", 0, 110]] + host,
    }


@pytest.mark.parametrize("host, name", [
    # wholly inside the innermost engine span
    ([["bench.poll", 5, 100], ["engine.poll", 6, 98],
      ["engine.retire", 8, 95], ["engine.retire.fetch", 9, 92]],
     "engine.retire.fetch"),
    # no engine span holds it all: down from bench.poll while one
    # child holds at least half of it
    ([["bench.poll", 5, 100], ["engine.poll", 6, 60],
      ["engine.tick.prep", 7, 10], ["engine.retire", 20, 45],
      ["bench.submit", 106, 2]], "engine.retire"),
    ([["bench.poll", 5, 100], ["engine.poll", 6, 40]], "bench.poll"),
    ([], "host"),
])
def test_a_gap_takes_the_innermost_span_that_holds_it(host, name):
    a = idle.attribute(_compact(host))
    assert a["idle_gaps"] == [[name, pytest.approx(90e-9)]]
    assert sum(a["idle_by_span"].values()) == pytest.approx(90e-9)


def test_idle_by_span_splits_a_gap_by_innermost_span():
    a = idle.attribute(_compact([
        ["bench.poll", 5, 100], ["engine.poll", 6, 98],
        ["engine.tick.prep", 20, 10], ["engine.retire", 40, 60],
        ["engine.retire.fetch", 50, 40]]))
    got = {k: round(v * 1e9) for k, v in a["idle_by_span"].items()}
    assert got == {"engine.poll": 10 + 10, "engine.tick.prep": 10,
                   "engine.retire": 10 + 10, "engine.retire.fetch": 40}


def test_host_span_readings():
    summary = {"host_spans": {
        "engine.poll": {"time_s": 4e-3, "count": 2.0},
        "engine.admit": {"time_s": 3e-4, "count": 1.5},
        "engine.retire.fetch": {"time_s": 1e-3, "count": 2.0},
        "engine.tick.prep": {"time_s": 1e-4, "count": 2.0},
    }}
    assert idle.admit_host_us(summary) == pytest.approx(200.0)
    assert idle.fetch_wait_us(summary) == pytest.approx(500.0)
    assert idle.poll_host_us(summary) == pytest.approx(1500.0)
    assert idle.span_mean_us(summary, "engine.tick.prep") == pytest.approx(
        50.0)
    # a program without the spans, or a run without a trace: nothing
    for empty in ({"host_spans": {}}, {}, None):
        assert idle.admit_host_us(empty) is None
        assert idle.fetch_wait_us(empty) is None
        assert idle.poll_host_us(empty) is None


def test_clock_shift_restores_launch_order():
    """A program the trace shows starting before the host call that
    launched it began is the two clocks' offset: the device's times move
    later by the largest such lead, and not at all without one."""
    calls = [1000, 3700, 5200, 9100, 10400]

    def compact(lead):
        return {
            "devices": [{"name": "/device:TPU:0", "ops": [["a", 0, 10]],
                         "modules": [["jit__chunk_fn(3)", c + 40 - lead, 20]
                                     for c in calls]}],
            "host": [["engine.tick.dispatch", c, 30] for c in calls],
        }

    launches = {"engine.tick.dispatch": "jit__chunk_fn"}
    assert idle.clock_shift(compact(0), launches) == 0
    assert idle.clock_shift(compact(100), launches) == 60
    # an execution launched before the capture began keeps the pairing
    extra = compact(100)
    extra["devices"][0]["modules"].insert(0, ["jit__chunk_fn(3)", 200, 20])
    assert idle.clock_shift(extra, launches) == 60


def test_a_shift_moves_the_idle_time_to_later_spans():
    host = [["bench.poll", 5, 100], ["engine.poll", 6, 98],
            ["engine.retire.fetch", 8, 42], ["engine.sample", 50, 50]]
    assert idle.attribute(_compact(host))["idle_gaps"][0][0] == "engine.poll"
    # busy [30, 40] once the device's times move 30 ns later
    a = idle.attribute(_compact(host), shift=30)
    got = {k: round(v * 1e9) for k, v in a["idle_by_span"].items()}
    assert got == {"host": 10, "bench.poll": 2, "engine.poll": 6,
                   "engine.retire.fetch": 32, "engine.sample": 50}
    assert a["idle_gaps"][0] == ["engine.sample", pytest.approx(70e-9)]
