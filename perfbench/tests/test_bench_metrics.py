"""Each metric reader on a canned run record."""

import math

import numpy as np
import pytest

from perfbench import harness, spec, work
from perfbench.systems import snn_stream_engine as sut

ROOT = harness.ROOT
CFG = spec.cell(spec.load(ROOT), ROOT, "c64-frames-max").config


def _result(i, ok=True, wait=0.004):
    return sut.Result(request_id=i, ok=ok, prediction=1,
                      spike_counts=np.zeros(2), events_per_layer=np.zeros(2),
                      queue_wait_s=wait, membrane_sum=np.zeros(2))


def _trace():
    return {
        "window_s": 2.0, "busy_s": 1.5,
        "programs": {"jit_admit_spikes": {"time_s": 1.4, "calls": 120},
                     "jit__chunk_fn": {"time_s": 0.1, "calls": 64}},
        "kernels": {"snn_chunk": {"time_s": 0.064, "calls": 64}},
        "device_ops": [], "idle_gaps": [],
    }


def _record(trace=None, n=20):
    """``n`` requests, one due every 10 ms; request i is submitted 1 ms
    late and back 50 + i ms after it was due; the last one failed."""
    reqs = []
    for i in range(n):
        r = harness.Request(index=i, due=i * 0.01)
        r.submitted = r.due + 0.001
        r.done = r.due + 0.050 + i * 0.001
        r.result = _result(i, ok=i < n - 1, wait=0.002 * i)
        reqs.append(r)
    cell = spec.Cell(name="c", chips=1, config=CFG, traffic={},
                     end_to_end=[], per_layer=[])
    return harness.Record(
        cell=cell, seconds=2.0, setup_s=15.5, requests=reqs,
        completed_in_window=124, tick={"ticks": 10, "host_prep_us": 20.0,
                                       "dispatch_us": 500.0,
                                       "stats_fetch_us": 900.0},
        system={"num_slots": 8, "chunk_steps": 5, "capacity": 4096,
                "addr_bytes": 2, "programs": dict(sut.PROGRAMS)},
        device_kind="TPU v5 lite", trace=trace)


def read(name, rec):
    return spec.reader(name).read(rec)


def test_latency_is_timed_from_the_due_time_and_a_failure_is_missing():
    rec = _record()
    lat = rec.latencies_s()
    # due to result, not submit to result
    assert lat[0] == pytest.approx(0.050)
    assert math.isinf(lat[-1])
    # nearest rank over 20: p50 is the 10th value, p95 the 19th
    assert harness.percentile(lat, 50) == pytest.approx(0.059)
    assert harness.percentile(lat, 95) == pytest.approx(0.068)
    assert math.isinf(harness.percentile(lat, 99))


def test_end_to_end_readers():
    rec = _record()
    assert read("windows_per_s", rec) == pytest.approx(62.0)
    assert read("setup_s", rec) == 15.5


def test_host_side_layer_readers():
    rec = _record()
    assert read("tick.host_us.tput", rec) == pytest.approx(520.0)


@pytest.mark.parametrize("name", ["admit.device_us.tput",
                                  "kernel.snn_chunk_us.tput",
                                  "snn_chunk_roofline",
                                  "device.idle_share.tput"])
def test_trace_readers_are_silent_without_a_trace(name):
    assert read(name, _record()) is None


def test_only_the_kernel_readers_may_be_absent():
    absent = {m for m in ("admit.device_us.tput", "kernel.snn_chunk_us.tput",
                          "snn_chunk_roofline", "device.idle_share.tput",
                          "tick.host_us.tput", "step.mfu.tput",
                          "windows_per_s", "setup_s")
              if getattr(spec.reader(m), "MAY_BE_ABSENT", False)}
    assert absent == {"kernel.snn_chunk_us.tput", "snn_chunk_roofline"}


def test_trace_readers():
    rec = _record(_trace())
    assert read("admit.device_us.tput", rec) == pytest.approx(1.4e6 / 120)
    assert read("kernel.snn_chunk_us.tput", rec) == pytest.approx(1000.0)
    assert read("device.idle_share.tput", rec) == pytest.approx(25.0)
    # 120 admissions x 25 steps over 64 calls > 40: capped at 8 x 5
    flops = 40 * work.flops_per_step(CFG["layer_sizes"])
    nbytes = work.chunk_bytes(CFG, 40, 8, 4096, 2)
    least, bound = work.least_time(flops, nbytes, work.peaks("TPU v5 lite"))
    assert bound == "compute"
    assert read("snn_chunk_roofline", rec) == pytest.approx(
        least / 1e-3 * 100)
    assert 0 < read("snn_chunk_roofline", rec) <= 100


def test_step_mfu_counts_dense_equivalent_flops():
    rec = _record()
    assert work.flops_per_window(CFG) == pytest.approx(
        25 * 2 * (4096 * 512 + 512 * 2))
    assert read("step.mfu.tput", rec) == pytest.approx(
        62.0 * work.flops_per_window(CFG) / 197e12 * 100)


def test_peaks_are_keyed_by_device_kind():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
