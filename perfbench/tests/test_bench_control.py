"""The comparison that decides ``correct`` finds the control and every
planted fault, and passes the program."""

import numpy as np
import pytest

from perfbench import compare, control, harness, spec
from perfbench.tests._tiny import run_tiny, tiny_cell

ROOT = harness.ROOT


def test_the_program_passes():
    out = run_tiny(tiny_cell())
    assert out["correct"] is True
    assert out["attempted"] > 50
    assert out["compared"]["missing"]["value"] == 0
    assert out["compared"]["layer0_events_gap"]["value"] == 0


def test_the_program_passes_open_loop():
    out = run_tiny(tiny_cell(arrival="poisson", rate_per_s=200.0))
    assert out["correct"] is True
    assert out["attempted"] > 50


@pytest.mark.parametrize("fault", control.FAULTS)
def test_a_planted_fault_is_not_correct(fault):
    out = run_tiny(tiny_cell(), system_override=control.broken(fault))
    assert out["correct"] is False
    assert out["attempted"] > 50


def test_a_request_that_never_comes_is_missing():
    cell = tiny_cell()

    def drop_first(system):
        poll = system.poll
        dropped = []

        def lossy():
            res = poll()
            if not dropped and res and res[0].request_id > 20:
                dropped.append(res[0].request_id)
                return res[1:]
            return res
        system.poll = lossy
        return system

    out = run_tiny(cell, system_override=drop_first)
    assert out["correct"] is False
    assert out["compared"]["missing"]["value"] == 1


@pytest.mark.parametrize("name", ["collision-64px", "collision-32px"])
def test_the_control_is_not_correct(name):
    """The reference at three-pass bfloat16 in the program's place, over
    256 requests of the configuration's own widths and inputs, read
    against the reference at "highest": it fails a limit."""
    doc = spec.load(ROOT)
    entry = next(c for c in doc["configs"] if c["name"] == name)
    cfg = spec.json.loads((ROOT / entry["file"]).read_text())
    cell = next(w for w in doc["workloads"] if w["config"] == name)
    mix = spec.cell(doc, ROOT, cell["name"]).traffic
    ref = spec.named("references", cfg["reference"])
    params = ref.make_params(cfg, harness.derive_key(2024))
    source = spec.named("inputs", mix["input"]).Source(
        mix, cfg["num_steps"], cfg["layer_sizes"][0],
        np.random.default_rng(2024))
    due = [source.draw() for _ in range(256)]
    hid, out, mem, pred = compare.reference_outputs(
        ref, params, source, due, "high", 128)
    sut = spec.named("systems", cfg["system"])
    results = {
        j: sut.Result(request_id=j, ok=True, prediction=int(pred[k]),
                      spike_counts=out[k],
                      events_per_layer=np.array(
                          [float(source.train_u8(j).sum()), hid[k]]),
                      queue_wait_s=0.0, membrane_sum=mem[k])
        for k, j in enumerate(due)
    }
    nums = compare.numbers(
        results, due, source,
        compare.reference_outputs(ref, params, source, due, "highest", 128))
    assert not compare.verdict(nums, cfg["limits"]), nums
    assert (nums["membrane_rel_gap_median"]
            > cfg["limits"]["membrane_rel_gap_median"])


def test_the_control_rounds_to_bfloat16_on_the_bits():
    import jax
    import jax.numpy as jnp

    ref = spec.named("references", "lif_mlp")
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 0.02
    np.testing.assert_array_equal(
        np.asarray(ref._to_bf16(x)),
        np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    err = np.abs(np.asarray(ref._three_pass(x)) - np.asarray(x))
    assert 0 < err.max() <= 2.0**-16 * np.abs(np.asarray(x)).max()


@pytest.mark.parametrize("counts, mem, decided", [
    ([3, 1], [0.0, 0.0], True),  # counts decide
    ([0, 0], [10.0, 5.0], True),  # a count tie the membrane sums decide
    ([0, 0], [10.0, 10.0 + 1e-4], False),  # within rounding of a tie
    ([2, 2], [4.0, 4.0], False),
], ids=["counts", "membranes", "near-tie", "exact-tie"])
def test_a_prediction_is_compared_unless_rounding_could_flip_it(
        counts, mem, decided):
    mem = np.array(mem)
    scale = max(np.abs(mem).max(), 1.0)
    assert compare._decided(np.array(counts, float), mem, scale) is decided


def test_the_reference_breaks_a_count_tie_on_the_membrane_sums():
    import jax.numpy as jnp

    ref = spec.named("references", "lif_mlp")
    counts = jnp.array([[3.0, 3.0], [3.0, 2.0], [0.0, 0.0]])
    memsum = jnp.array([[100.0, 100.01], [1.0, 50.0], [-4.0, -3.0]])
    np.testing.assert_array_equal(np.asarray(ref.predict(counts, memsum)),
                                  [1, 0, 1])
    # adding the sums to the counts at 1e-6 in float32 loses the first
    assert int(jnp.argmax(counts[0] + 1e-6 * memsum[0])) == 0
