"""repro-lint static analysis: per-rule lint fixtures, suppression
syntax, kernel VMEM/SMEM budget plans, recompile / donation / AER
runtime contracts, and the repo-wide zero-findings invariant."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import (
    DEFAULT_SMEM_BUDGET,
    DEFAULT_VMEM_BUDGET,
    ContractViolation,
    RecompileDetector,
    RULES,
    aer_bounds_report,
    check_aer_bounds,
    check_kernel_budgets,
    donation_report,
    lint_paths,
    lint_source,
    runtime_donation_check,
    verify_donation,
)
from repro.analysis.kernel_budget import (
    KERNEL_PLANNERS,
    VMEM_CAPACITY,
    plan_findings,
    plan_snn_chunk,
    snn_chunk_staging,
)
from repro.events import aer, runtime


def codes(src, path="fixture.py"):
    return sorted(f.code for f in lint_source(src, path).findings)


# ------------------------------------------------------------------ lint rules
def test_rl000_parse_error():
    assert codes("def f(:\n") == ["RL000"]


def test_rl101_host_call_in_jit_body():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "import time\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    print(x)\n"
        "    time.sleep(0.1)\n"
        "    return np.sum(x)\n"
    )
    assert codes(src) == ["RL101", "RL101", "RL101"]


def test_rl101_pallas_kernel_body():
    src = (
        "import numpy as np\n"
        "from jax.experimental import pallas as pl\n"
        "def kernel(x_ref, o_ref):\n"
        "    o_ref[...] = np.tanh(x_ref[...])\n"
        "def run(x):\n"
        "    return pl.pallas_call(kernel, out_shape=x)(x)\n"
    )
    assert "RL101" in codes(src)


def test_rl101_host_call_outside_jit_ok():
    src = "import numpy as np\ndef f(x):\n    return np.sum(x)\n"
    assert codes(src) == []


def test_rl102_tracer_leak():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x) + x.item()\n"
    )
    assert codes(src) == ["RL102", "RL102"]


def test_rl103_traced_branch():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    while x < 3:\n"
        "        x = x + 1\n"
        "    return x\n"
    )
    assert codes(src) == ["RL103", "RL103"]


def test_rl103_static_shape_branch_ok():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x.shape[0] > 1:\n"
        "        return x[0]\n"
        "    return x\n"
    )
    assert codes(src) == []


def test_rl103_static_argnames_exempt():
    src = (
        "import jax\n"
        "from functools import partial\n"
        '@partial(jax.jit, static_argnames=("n",))\n'
        "def f(x, n):\n"
        "    if n > 2:\n"
        "        return x\n"
        "    return x * n\n"
    )
    assert codes(src) == []


_DONATE_PRELUDE = (
    "import jax\n"
    "def step(state, x):\n"
    "    return state + x\n"
    "step_j = jax.jit(step, donate_argnums=(0,))\n"
)


def test_rl104_at_set_on_donated():
    src = _DONATE_PRELUDE + (
        "def run(state, x):\n"
        "    out = step_j(state, x)\n"
        "    return out, state.at[0].set(1.0)\n"
    )
    assert "RL104" in codes(src)


def test_rl105_donated_reuse():
    src = _DONATE_PRELUDE + (
        "def run(state, x):\n"
        "    y = step_j(state, x)\n"
        "    return state + y\n"
    )
    assert "RL105" in codes(src)


def test_rl105_device_get_after_donation():
    # the snapshot-path hazard: fetching donated device state on the
    # host *after* the donating dispatch reads freed storage
    src = _DONATE_PRELUDE + (
        "def snapshot(state, x):\n"
        "    out = step_j(state, x)\n"
        "    host = jax.device_get(state)\n"
        "    return out, host\n"
    )
    res = lint_source(src, "fixture.py")
    assert [f.code for f in res.findings] == ["RL105"]
    assert "device_get" in res.findings[0].message


def test_rl105_device_get_before_donation_ok():
    # the correct snapshot ordering: host fetch first, dispatch second
    src = _DONATE_PRELUDE + (
        "def snapshot(state, x):\n"
        "    host = jax.device_get(state)\n"
        "    out = step_j(state, x)\n"
        "    return out, host\n"
    )
    assert codes(src) == []


def test_rl105_loop_rebind_ok():
    # the engine/train-loop idiom: the loop rebinds the donated buffer
    # from the call's output each iteration, so reuse is fine
    src = _DONATE_PRELUDE + (
        "def run(state, xs):\n"
        "    for x in xs:\n"
        "        state = step_j(state, x)\n"
        "    return state\n"
    )
    assert codes(src) == []


def test_rl106_float64():
    src = (
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        '    return jnp.asarray(x, dtype="float64") + jnp.float64(0)\n'
    )
    assert codes(src) == ["RL106", "RL106"]


def test_rl106_host_numpy_f64_ok():
    src = "import numpy as np\ndef f(x):\n    return np.float64(x)\n"
    assert codes(src) == []


def test_rl107_unshaped_blockspec():
    src = (
        "from jax.experimental import pallas as pl\n"
        "def f():\n"
        "    return pl.BlockSpec()\n"
    )
    assert codes(src) == ["RL107"]


def test_rl107_shaped_or_memory_space_ok():
    src = (
        "from jax.experimental import pallas as pl\n"
        "from jax.experimental.pallas import tpu as pltpu\n"
        "def f():\n"
        "    a = pl.BlockSpec((8, 128), lambda i: (i, 0))\n"
        "    b = pl.BlockSpec(memory_space=pltpu.TPUMemorySpace.SMEM)\n"
        "    return a, b\n"
    )
    assert codes(src) == []


def test_rl201_unused_import():
    src = "import os\nimport sys\nprint(sys.argv)\n"
    assert codes(src) == ["RL201"]


def test_rl201_init_py_exempt():
    assert codes("import os\n", path="pkg/__init__.py") == []


def test_rl202_unreachable():
    src = "def f():\n    return 1\n    x = 2\n"
    assert codes(src) == ["RL202"]


# ------------------------------------------------------------------ suppression
def test_line_suppression_moves_to_suppressed():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    print(x)  # repro-lint: disable=RL101 -- debugging aid\n"
        "    return x\n"
    )
    res = lint_source(src, "fixture.py")
    assert [f.code for f in res.findings] == []
    assert [f.code for f in res.suppressed] == ["RL101"]


def test_file_level_suppression():
    src = (
        "# repro-lint: disable-file=RL201 -- fixture\n"
        "import os\n"
        "import sys\n"
    )
    res = lint_source(src, "fixture.py")
    assert [f.code for f in res.findings] == []
    assert sorted(f.code for f in res.suppressed) == ["RL201", "RL201"]


def test_unrelated_suppression_does_not_hide():
    src = "import os  # repro-lint: disable=RL106 -- wrong code\n"
    assert codes(src) == ["RL201"]


def test_rules_table_covers_emitted_codes():
    assert {"RL000", "RL101", "RL102", "RL103", "RL104", "RL105", "RL106",
            "RL107", "RL201", "RL202"} <= set(RULES)


# ------------------------------------------------------------------ kernel budgets
def test_kernel_budgets_all_kernels_fit():
    plans, findings = check_kernel_budgets()
    assert [f.render() for f in findings] == []
    assert {p.kernel for p in plans} == set(KERNEL_PLANNERS)
    for p in plans:
        assert p.errors == []
        # a kernel's budget is the scoped limit it asks for, else the
        # compiler's default, and never more than the core holds
        budget = p.vmem_limit_bytes or DEFAULT_VMEM_BUDGET
        assert 0 < p.vmem_bytes <= budget <= VMEM_CAPACITY
        assert p.smem_bytes <= DEFAULT_SMEM_BUDGET
        assert p.grid, p.kernel


def test_kernel_budget_overflow_flagged():
    # a core of 1 KiB: no kernel's working set or asked limit fits
    plans, findings = check_kernel_budgets(vmem_budget=1024,
                                           vmem_capacity=1024)
    assert findings and all(f.code == "RB301" for f in findings)
    assert len(findings) == len(plans)


def test_snn_chunk_plan_shape():
    (plan,), findings = check_kernel_budgets(kernels=["snn_chunk"])
    assert not findings
    roles = {b.role for b in plan.buffers}
    assert "scratch" in roles
    assert plan.num_scalar_prefetch >= 1
    assert plan.smem_bytes > 0


@pytest.mark.parametrize("sizes,steps_per_block", [
    ((4096, 512, 2), 5),  # 64 px: a slot's whole chunk of rows in SMEM
    ((16384, 512, 2), 1),  # 128 px: a step at a time, a 32 MiB slab
])
def test_snn_chunk_priced_at_the_served_geometry(sizes, steps_per_block):
    """8 slots, Tc=5, C = fan-in: the planner's price is what the kernel
    hands Mosaic, nothing is over, and the limit covers the set."""
    plan = plan_snn_chunk(sizes, slots=8, chunk_steps=5)
    assert plan_findings(plan) == []
    staging = snn_chunk_staging(sizes[:-1], sizes[1:], 8, 5, sizes[0])
    assert staging.steps_per_block == steps_per_block
    assert plan.grid == (8, 5 // steps_per_block)
    assert plan.smem_bytes == staging.smem_bytes <= DEFAULT_SMEM_BUDGET
    assert plan.vmem_bytes == staging.vmem_bytes
    assert plan.vmem_limit_bytes == staging.vmem_limit_bytes
    assert staging.vmem_bytes < staging.vmem_limit_bytes <= VMEM_CAPACITY
    assert staging.weight_bytes == sizes[0] * 512 * 4
    assert staging.event_row_bytes == 8 * 5 * sizes[0] * 8


def test_snn_chunk_slab_past_the_core_is_a_finding():
    """A 65536-wide layer 0 (a 128 MiB slab) is priced, not crashed on:
    the limit it would need passes the core's VMEM."""
    plan = plan_snn_chunk((65536, 512, 2), slots=8, chunk_steps=5,
                          capacity=4096)
    assert plan.vmem_limit_bytes > VMEM_CAPACITY
    assert [f.code for f in plan_findings(plan)] == ["RB301"]


# ------------------------------------------------------------------ recompile detector
def test_recompile_detector_catches_shape_unstable_fn():
    @jax.jit
    def f(x):
        return x * 2.0

    with RecompileDetector() as det:
        det.track("f", f, allowed=1)  # cold start
        for n in (4, 8, 16):  # shape-unstable: one compile per shape
            f(jnp.zeros((n,), jnp.float32))
    assert det.cache_growth("f") == 3
    assert det.unexpected()
    with pytest.raises(ContractViolation):
        det.raise_on_unexpected()


def test_recompile_detector_clean_on_stable_shapes():
    @jax.jit
    def f(x):
        return x + 1.0

    x = jnp.zeros((8,), jnp.float32)
    f(x)  # warm outside the region
    with RecompileDetector() as det:
        det.track("f", f, allowed=0)
        for _ in range(5):
            f(x)
    rep = det.report()
    assert rep["tracked"]["f"]["unexpected"] == 0
    assert det.unexpected() == []


def test_recompile_detector_freezes_growth_at_exit():
    @jax.jit
    def f(x):
        return x - 1.0

    f(jnp.zeros((4,), jnp.float32))
    with RecompileDetector() as det:
        det.track("f", f, allowed=0)
    f(jnp.zeros((16,), jnp.float32))  # after the region: must not count
    assert det.cache_growth("f") == 0
    assert det.unexpected() == []


# ------------------------------------------------------------------ donation
def _donating_fn():
    def body(state, x):
        return state + x

    return jax.jit(body, donate_argnums=(0,))


def test_donation_report_and_verify():
    fn = _donating_fn()
    args = (jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.float32))
    rep = verify_donation(fn, args, expect_donated=[0])
    assert rep["donated_argnums"] == [0]
    with pytest.raises(ContractViolation):
        verify_donation(fn, args, expect_donated=[0, 1])


def test_runtime_donation_check():
    fn = _donating_fn()
    state = jax.device_put(np.ones((8,), np.float32))
    x = jax.device_put(np.ones((8,), np.float32))
    out = runtime_donation_check(fn, (state, x), donated=[0])
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert state.is_deleted()

    nodonate = jax.jit(lambda s, x: s + x)
    s2 = jax.device_put(np.ones((8,), np.float32))
    with pytest.raises(ContractViolation):
        runtime_donation_check(nodonate, (s2, x), donated=[0])


def test_engine_chunk_donation_contract():
    # the contract the tick loop relies on: states + meta are donated,
    # weights (prepared) and the spike ring are not
    from repro.core import snn
    from repro.serving.snn_engine import SNNStreamEngine

    cfg = snn.SNNConfig(layer_sizes=(64, 24, 2), num_steps=6)
    params = snn.init_params(jax.random.PRNGKey(0), cfg)
    eng = SNNStreamEngine(params, cfg, num_slots=2, chunk_steps=3)
    trains = [np.zeros((6, 64), np.float32)] * 2
    rep = donation_report(eng._chunk, *eng.staged_chunk_args(trains))
    assert rep["donated_argnums"] == [1, 3]


# ------------------------------------------------------------------ AER bounds
def test_aer_bounds_collision_config_clean():
    from repro.configs.collision_snn import CONFIG

    assert check_aer_bounds(CONFIG.layer_sizes) == []
    rep = aer_bounds_report(CONFIG.layer_sizes, num_steps=CONFIG.num_steps)
    assert rep["ok"]
    assert [lay["addr_fits"] for lay in rep["layers"]] == [True] * 3


def test_aer_bounds_flags_overflow():
    # force an int16-indexed layer wider than int16 can address
    wide = int(np.iinfo(np.int16).max) + 2
    if np.dtype(aer.addr_dtype_for(wide)) != np.dtype(np.int16):
        pytest.skip("addr_dtype_for already promotes past int16")
    assert check_aer_bounds([wide])


def test_check_addr_dtype_guard():
    aer.check_addr_dtype(4096, jnp.int16)  # fits
    with pytest.raises(ValueError, match="int16"):
        aer.check_addr_dtype(70_000, jnp.int16)


def test_encode_step_table_rejects_narrow_dtype():
    spikes = jnp.zeros((2, 70_000), jnp.float32)
    with pytest.raises(ValueError, match="silently wrap"):
        runtime.encode_step_table(spikes, capacity=8, addr_dtype=jnp.int16)


# ------------------------------------------------------------------ repo-wide
def test_repo_tree_is_lint_clean():
    from repro.analysis.__main__ import REPO_ROOT

    res = lint_paths([REPO_ROOT / "src" / "repro"], rel_to=REPO_ROOT)
    assert [f.render() for f in res.findings] == []


def test_cli_exits_zero_and_writes_json(tmp_path):
    from repro.analysis.__main__ import main

    out = tmp_path / "report.json"
    rc = main(["--json", str(out), "--no-kernels", "--no-aer"])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["schema"] == "repro-analysis/v1"
    assert doc["counts"]["findings"] == 0
    assert doc["counts"]["new"] == 0


def test_cli_full_run_reports_kernels():
    from repro.analysis.__main__ import run

    doc = run()
    assert doc["counts"]["findings"] == 0
    assert {p["kernel"] for p in doc["kernels"]} == set(KERNEL_PLANNERS)
    assert all(p["errors"] == [] for p in doc["kernels"])
