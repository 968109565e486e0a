"""The command's contract: no result without a TPU or outside a checkout,
and a last line with the contract's keys alone."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import harness
from perfbench.tests._tiny import run_tiny, tiny_cell

ROOT = harness.ROOT
ARGS = ["--workload", "c64-frames-max", "--seed", str(2**31 + 11),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=240)


def _printed_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return isinstance(json.loads(lines[-1]), dict)
    except json.JSONDecodeError:
        return False


def test_no_result_and_a_nonzero_exit_without_a_tpu():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_no_result_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not _printed_result(p.stdout)


def test_the_chip_check_refuses_the_cpu():
    import jax

    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(harness.NoChip):
        harness.check_chips(1)


def test_last_line_has_the_contract_keys_alone(monkeypatch, capsys):
    import jax

    cell = tiny_cell()
    monkeypatch.setattr(harness.spec, "cell", lambda *a, **k: cell)
    monkeypatch.setattr(harness, "check_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "")
    assert harness.main(ARGS[:-3] + ["0.5", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"windows_per_s", "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(set(v) == {"value", "limit"}
               for v in res["compared"].values())
    tail = err.strip().splitlines()[-len(res["compared"]):]
    assert all(line.startswith("compared ") for line in tail)


def test_process_age_is_read_from_the_kernel():
    age = harness.process_age_s()
    assert age is None or 0 <= age < 1e6


def test_a_run_that_leaves_its_stated_path_stops():
    def demoted(system):
        system.unsound = lambda platform: ["demotions 1"]
        return system

    with pytest.raises(harness.RunError, match="demotions 1"):
        run_tiny(tiny_cell(), system_override=demoted)


def test_the_fused_kernel_is_the_stated_path_on_a_tpu():
    from perfbench.systems import snn_stream_engine as sut

    health = {"backend": "jnp", "demotions": 0.0, "retries": 0.0,
              "quarantined": 0.0, "steady_state_recompiles": 0}
    system = object.__new__(sut.System)
    system.health = lambda: health
    assert system.unsound("cpu") == []
    assert "not the fused kernel" in system.unsound("tpu")[0]
    health.update(backend="fused", retries=2.0)
    assert system.unsound("tpu") == ["retries 2"]


def test_a_listed_metric_that_reads_nothing_stops_the_run():
    cell = tiny_cell()
    cell = dataclasses.replace(cell, end_to_end=cell.end_to_end + [
        {"name": "admit.device_us.tput", "unit": "us"}])
    with pytest.raises(harness.RunError, match="admit.device_us.tput"):
        run_tiny(cell)


def test_a_kernel_metric_may_be_absent():
    cell = tiny_cell()
    cell = dataclasses.replace(cell, end_to_end=cell.end_to_end + [
        {"name": "snn_chunk_roofline", "unit": "%"}])
    out = run_tiny(cell)
    assert set(out["metrics"]) == {"windows_per_s", "setup_s"}


def test_the_stall_tool_places_long_calls():
    import jax

    from perfbench.tools import stalls

    row = stalls.one_run(tiny_cell(), 5, 0.6, jax.devices()[:1],
                         stall_s=0.0005, period_s=0.002)
    assert row["correct"] is True and row["polls"] > 0
    assert row["stalls"] > 0 and row["tick_max_ms"]["fetch"] > 0
    longest = row["longest"][0]
    assert longest["kind"] in ("poll", "submit", "harness")
    assert longest["ms"] >= 0.5
