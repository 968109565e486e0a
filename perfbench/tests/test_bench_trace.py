"""The trace reducer counts nested device operations once; pinned on a
slice of a trace recorded on a TPU v5e (c64-frames-max, 0.25 s)."""

import json
import pathlib

import pytest

from perfbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _recorded():
    return json.loads((DATA / "trace_c64_frames_max.json").read_text())


def test_op_names_are_the_hlo_names():
    assert trace.op_name(
        "%fusion.20 = s32[102400]{0} fusion(s32[25,4096]{1,0} %a), "
        "kind=kCustom") == "fusion.20"
    assert trace.op_name("snn_chunk.1") == "snn_chunk.1"


def test_a_fusion_inside_a_while_is_counted_once():
    compact = {
        "devices": [{
            "name": "/device:TPU:0",
            "modules": [["jit_admit_spikes(7)", 0, 100],
                        ["jit__chunk_fn(9)", 150, 20]],
            "ops": [["while.4", 0, 90], ["fusion.20", 10, 70],
                    ["fusion.1", 90, 10], ["snn_chunk.1", 152, 16]],
        }],
        "host": [["bench.traced", 0, 200], ["bench.poll", 100, 60]],
    }
    r = trace.reduce(compact, kernels=("snn_chunk",))
    assert r["busy_s"] == pytest.approx(116e-9)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["programs"]["jit_admit_spikes"] == {
        "time_s": pytest.approx(100e-9), "calls": 1}
    assert r["programs"]["jit__chunk_fn"]["calls"] == 1
    assert r["kernels"]["snn_chunk"] == {
        "time_s": pytest.approx(16e-9), "calls": 1}
    assert [k for k, _ in r["device_ops"]] == [
        "jit_admit_spikes/while.4", "jit__chunk_fn/snn_chunk.1",
        "jit_admit_spikes/fusion.1"]
    # gaps: 100-152 while the host polled, 168-200 at the end
    assert r["idle_gaps"][0] == ["bench.poll", pytest.approx(52e-9)]


def test_recorded_chip_trace():
    """A reducer that sums nested operations adds ``while.4`` and the
    ``fusion.20`` nested in it, twice the admission's device time; here
    each counts once."""
    compact = _recorded()
    ops = compact["devices"][0]["ops"]
    nested = sum(d for n, _, d in ops if n == "fusion.20")
    r = trace.reduce(compact, kernels=("snn_chunk",))
    assert r["window_s"] == pytest.approx(0.25)
    assert r["busy_s"] == pytest.approx(0.237927911, rel=1e-9)
    admit = r["programs"]["jit_admit_spikes"]
    chunk = r["programs"]["jit__chunk_fn"]
    # 16 admissions touch the window, the first and last cut by its edges
    assert admit["calls"] == pytest.approx(15.652770233, rel=1e-9)
    assert chunk["calls"] == 10
    assert admit["time_s"] == pytest.approx(0.23001446, rel=1e-9)
    assert admit["time_s"] / admit["calls"] == pytest.approx(0.014695, rel=1e-3)
    assert r["kernels"]["snn_chunk"]["calls"] == 10
    assert r["kernels"]["snn_chunk"]["time_s"] == pytest.approx(
        0.007578269, rel=1e-9)
    top = dict(r["device_ops"])
    assert top["jit_admit_spikes/while.4"] == pytest.approx(
        0.212529315, rel=1e-9)
    # the fusion inside the while is not listed again at full length
    assert top.get("jit_admit_spikes/fusion.20", 0.0) < 0.01 * nested
    assert sum(top.values()) <= r["busy_s"] * 1.01
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert all(name.startswith("bench.") for name, _ in r["idle_gaps"])


def test_an_empty_trace_reads_nothing():
    r = trace.reduce({"devices": [], "host": []})
    assert r["busy_s"] == 0.0 and r["window_s"] is None
