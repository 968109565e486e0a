"""The fused kernel's time per layer-0 event, on hand-made run records."""

import numpy as np
import pytest

from perfbench import harness, spec
from perfbench.systems import snn_stream_engine as sut

ROOT = harness.ROOT
NAME = "kernel.snn_chunk_ns_per_event.tput"


def _record(trace, events=(), cell="c128-frames-max"):
    """One served request per entry of ``events`` (its layer-0 events over
    the window), and one that failed, whose events do not count."""
    cfg = spec.cell(spec.load(ROOT), ROOT, cell).config
    reqs = []
    for i, ev in enumerate(list(events) + [10**9]):
        r = harness.Request(index=i, due=0.0)
        r.result = sut.Result(
            request_id=i, ok=i < len(events), prediction=0,
            spike_counts=np.zeros(2), events_per_layer=np.array([ev, 7.0]),
            queue_wait_s=0.0, membrane_sum=np.zeros(2))
        reqs.append(r)
    return harness.Record(
        cell=spec.Cell(name=cell, chips=1, config=cfg, traffic={},
                       end_to_end=[], per_layer=[]),
        seconds=2.0, setup_s=15.0, requests=reqs, completed_in_window=10,
        tick={"ticks": 0}, device_kind="TPU v5 lite", trace=trace,
        system={"num_slots": 8, "chunk_steps": 5,
                "capacity": cfg["layer_sizes"][0], "addr_bytes": 2,
                "programs": dict(sut.PROGRAMS)})


def _trace(kernel_s, calls, admissions):
    return {"window_s": 2.0, "busy_s": 1.0, "device_ops": [], "idle_gaps": [],
            "programs": {"jit_admit_spikes": {"time_s": 0.01,
                                              "calls": admissions}},
            "kernels": {"snn_chunk": {"time_s": kernel_s, "calls": calls}}}


@pytest.mark.parametrize("admissions,slot_steps", [
    (64, 40),  # 64 x 25 / 32 = 50 slot-steps a call: capped at 8 x 5
    (32, 25),  # 32 x 25 / 32: the engine was not full
])
def test_time_per_call_over_events_per_call(admissions, slot_steps):
    # 32 calls of 3.2 ms; requests of 6,000 and 7,000 events a step
    rec = _record(_trace(0.1024, 32, admissions),
                  events=(25 * 6000.0, 25 * 7000.0))
    expected = 3.2e6 / (slot_steps * 6500.0)
    assert spec.reader(NAME).read(rec) == pytest.approx(expected)


def test_silent_without_a_trace_a_kernel_or_an_event():
    reader = spec.reader(NAME)
    assert reader.MAY_BE_ABSENT
    assert reader.read(_record(None, events=(100.0,))) is None
    no_kernel = _trace(0.1, 32, 64)
    no_kernel["kernels"] = {}
    assert reader.read(_record(no_kernel, events=(100.0,))) is None
    assert reader.read(_record(_trace(0.1, 32, 64), events=())) is None
    assert reader.read(_record(_trace(0.1, 32, 64), events=(0.0,))) is None
