"""Fused event-driven chunk kernel: parity with ``runtime.run_chunk`` in
interpret mode across the semantic matrix (empty/full event streams,
frozen continuous-batching slots, refractory, both reset modes, Q1.15),
plus the O(K) ``step_events`` rewrite and the capacity autotuner."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import neuron, snn
from repro.events import capacity as cap_mod
from repro.events import runtime

RNG = np.random.default_rng(11)


def _spikes(Tc, B, K, rate, signed=False):
    s = (RNG.random((Tc, B, K)) < rate).astype(np.float32)
    if signed:
        s *= RNG.choice([-1.0, 1.0], (Tc, B, K))
    return jnp.asarray(s)


def _states(cfg, B, *, nonzero=False, refrac=False):
    states = runtime.init_states(cfg, B)
    if nonzero:
        out = []
        for i, st in enumerate(states):
            u = jnp.asarray(
                RNG.normal(0, 0.3, st.u.shape).astype(np.float32)
            )
            r = (
                jnp.asarray(
                    RNG.integers(0, 3, st.refrac.shape).astype(np.int32)
                )
                if refrac
                else st.refrac
            )
            out.append(neuron.NeuronState(u=u, refrac=r))
        return out
    return states


def _assert_chunk_parity(cfg, spikes, states, active=None, capacities=None):
    sj, mj, pj, ej = runtime.run_chunk(
        params_for(cfg), states, spikes, cfg,
        active=active, capacities=capacities, backend="jnp",
    )
    sf, mf, pf, ef = runtime.run_chunk(
        params_for(cfg), states, spikes, cfg,
        active=active, capacities=capacities, backend="fused",
    )
    np.testing.assert_allclose(
        np.asarray(mf), np.asarray(mj), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(pf), np.asarray(pj))
    np.testing.assert_allclose(np.asarray(ef), np.asarray(ej))
    for a, b in zip(sf, sj):
        np.testing.assert_allclose(
            np.asarray(a.u), np.asarray(b.u), atol=1e-5, rtol=1e-5
        )
        np.testing.assert_array_equal(
            np.asarray(a.refrac), np.asarray(b.refrac)
        )


_PARAM_CACHE = {}


def params_for(cfg):
    key = (cfg.layer_sizes, cfg.quant_q115)
    if key not in _PARAM_CACHE:
        _PARAM_CACHE[key] = snn.init_params(jax.random.PRNGKey(5), cfg)
    return _PARAM_CACHE[key]


# ------------------------------------------------------------- parity matrix
@pytest.mark.parametrize("rate", [0.0, 0.3, 1.0])
def test_fused_parity_across_rates(rate):
    """Empty, sparse, and full event streams."""
    cfg = snn.SNNConfig(layer_sizes=(48, 16, 2), num_steps=6)
    _assert_chunk_parity(cfg, _spikes(6, 3, 48, rate), _states(cfg, 3))


@pytest.mark.parametrize("reset", ["zero", "subtract"])
def test_fused_parity_reset_modes(reset):
    cfg = snn.SNNConfig(layer_sizes=(40, 12, 2), num_steps=5, reset=reset)
    _assert_chunk_parity(
        cfg, _spikes(5, 2, 40, 0.4), _states(cfg, 2, nonzero=True)
    )


def test_fused_parity_refractory():
    """refractory > 0, including nonzero incoming countdowns."""
    cfg = snn.SNNConfig(layer_sizes=(40, 12, 2), num_steps=8,
                        refractory_steps=2)
    _assert_chunk_parity(
        cfg, _spikes(8, 2, 40, 0.6),
        _states(cfg, 2, nonzero=True, refrac=True),
    )


def test_fused_parity_q115():
    cfg = snn.SNNConfig(layer_sizes=(48, 16, 2), num_steps=6,
                        quant_q115=True)
    _assert_chunk_parity(cfg, _spikes(6, 2, 48, 0.3), _states(cfg, 2))


def test_fused_parity_lapicque():
    cfg = snn.SNNConfig(layer_sizes=(32, 10, 2), num_steps=5,
                        neuron_kind="lapicque")
    _assert_chunk_parity(cfg, _spikes(5, 2, 32, 0.3), _states(cfg, 2))


def test_fused_parity_three_layers():
    cfg = snn.SNNConfig(layer_sizes=(40, 20, 10, 2), num_steps=5)
    _assert_chunk_parity(cfg, _spikes(5, 2, 40, 0.3), _states(cfg, 2))


def test_fused_parity_frozen_slots():
    """Continuous batching: frozen slots hold state, emit nothing."""
    cfg = snn.SNNConfig(layer_sizes=(48, 16, 2), num_steps=6)
    states = _states(cfg, 4, nonzero=True, refrac=False)
    active = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    spikes = _spikes(6, 4, 48, 0.5)
    _assert_chunk_parity(cfg, spikes, states, active=active)
    # frozen slots explicitly: held state, zero spikes/events, pinned mem
    sf, mf, pf, ef = runtime.run_chunk(
        params_for(cfg), states, spikes, cfg, active=active,
        backend="fused",
    )
    for i, st in enumerate(sf):
        np.testing.assert_array_equal(
            np.asarray(st.u[1]), np.asarray(states[i].u[1])
        )
    assert not np.asarray(pf[:, 1]).any()
    assert not np.asarray(ef[:, :, 1]).any()
    np.testing.assert_array_equal(
        np.asarray(mf[:, 1]),
        np.broadcast_to(np.asarray(states[-1].u[1]), mf[:, 1].shape),
    )


@pytest.mark.parametrize("smem_budget,steps_per_block", [
    (None, 5),  # the rows of the whole chunk fit SMEM: one block a slot
    (8192, 1),  # they do not: the planner stages them a step at a time
])
def test_fused_staging_matches_oracle_and_reference(
    monkeypatch, smem_budget, steps_per_block
):
    """Both stagings the planner derives from the shapes give
    ``run_chunk``'s jnp oracle and the benchmark's plain reference
    (``perfbench/references/lif_mlp.py``) on its seeded random weights.
    The per-step side is forced by shrinking the planner's SMEM line, as
    a 16384-wide layer 0 forces it on the chip."""
    from perfbench.references import lif_mlp
    from repro.analysis import kernel_budget

    if smem_budget is not None:
        monkeypatch.setattr(kernel_budget, "DEFAULT_SMEM_BUDGET", smem_budget)
    sizes, B, Tc = (256, 32, 2), 3, 5
    plan = kernel_budget.snn_chunk_staging(sizes[:-1], sizes[1:], B, Tc, 256)
    assert plan.steps_per_block == steps_per_block
    jax.clear_caches()  # the staging is fixed when the kernel is traced

    cfg = snn.SNNConfig(layer_sizes=sizes, num_steps=Tc)
    ref_cfg = {"layer_sizes": list(sizes), "beta": 0.9, "threshold": 1.0,
               "out_gain": 8.0}
    params = lif_mlp.make_params(ref_cfg, jax.random.PRNGKey(16))
    spikes = _spikes(Tc, B, sizes[0], 0.4)
    states = runtime.init_states(cfg, B)
    outs = {
        backend: runtime.run_chunk(params, states, spikes, cfg,
                                   backend=backend)
        for backend in ("jnp", "fused")
    }
    (sf, mf, pf, ef), (sj, mj, pj, ej) = outs["fused"], outs["jnp"]
    np.testing.assert_allclose(np.asarray(mf), np.asarray(mj),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(pf), np.asarray(pj))
    np.testing.assert_array_equal(np.asarray(ef), np.asarray(ej))
    for a, b in zip(sf, sj):
        np.testing.assert_allclose(np.asarray(a.u), np.asarray(b.u),
                                   atol=1e-5, rtol=1e-5)

    hid, counts, memsum, _ = lif_mlp.forward(params, spikes)
    np.testing.assert_array_equal(np.asarray(ef)[:, 1].sum(0),
                                  np.asarray(hid))
    np.testing.assert_array_equal(np.asarray(pf).sum(0), np.asarray(counts))
    np.testing.assert_allclose(np.asarray(mf).sum(0), np.asarray(memsum),
                               atol=1e-4, rtol=1e-5)
    assert np.asarray(counts).sum() > 0  # the output layer fired


def test_fused_rejects_truncating_hidden_capacity():
    """The fused kernel cannot truncate hidden layers (dense in-VMEM
    matvecs); a plan that would make fused and jnp diverge must be
    rejected loudly, not executed platform-dependently."""
    cfg = snn.SNNConfig(layer_sizes=(48, 16, 2), num_steps=4)
    spikes = _spikes(4, 2, 48, 0.5)
    with pytest.raises(ValueError, match="hidden"):
        runtime.run_chunk(
            params_for(cfg), _states(cfg, 2), spikes, cfg,
            capacities=(48, 8), backend="fused",
        )
    # default autotune plans are fused-safe: hidden caps pinned at fan-in
    plan = cap_mod.autotune(
        params_for(cfg), cfg, spikes, percentile=50.0, safety=1.0, align=8
    )
    assert plan.capacities[1] == cfg.layer_sizes[1]
    runtime.run_chunk(
        params_for(cfg), _states(cfg, 2), spikes, cfg,
        capacities=plan.capacities, backend="fused",
    )


def test_fused_parity_with_truncating_capacity():
    """capacities[0] below the event count: both paths drop the same
    (latest-address) events and report the same truncated counts."""
    cfg = snn.SNNConfig(layer_sizes=(48, 16, 2), num_steps=6)
    spikes = _spikes(6, 3, 48, 0.9)
    caps = (16, 16)
    _assert_chunk_parity(cfg, spikes, _states(cfg, 3), capacities=caps)
    _, _, _, ej = runtime.run_chunk(
        params_for(cfg), _states(cfg, 3), spikes, cfg,
        capacities=caps, backend="jnp",
    )
    assert np.asarray(ej)[:, 0].max() <= caps[0]


def test_fused_chunk_state_carry_matches_whole_window():
    """Two fused chunks == one fused window (VMEM state round-trips
    exactly through the u_fin/refrac_fin outputs)."""
    cfg = snn.SNNConfig(layer_sizes=(40, 12, 2), num_steps=10,
                        refractory_steps=2)
    params = params_for(cfg)
    spikes = _spikes(10, 2, 40, 0.4)
    s0 = _states(cfg, 2)
    _, m_all, p_all, _ = runtime.run_chunk(
        params, s0, spikes, cfg, backend="fused"
    )
    s_mid, m1, p1, _ = runtime.run_chunk(
        params, s0, spikes[:4], cfg, backend="fused"
    )
    _, m2, p2, _ = runtime.run_chunk(
        params, s_mid, spikes[4:], cfg, backend="fused"
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([m1, m2])), np.asarray(m_all),
        atol=1e-5, rtol=1e-5,
    )
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([p1, p2])), np.asarray(p_all)
    )


# ------------------------------------------------------- O(K) step_events
@pytest.mark.parametrize("cap", [1, 7, 20, 33])
def test_step_events_matches_argsort_oracle(cap):
    x = jnp.asarray(
        RNG.normal(size=(4, 5, 33))
        * (RNG.random((4, 5, 33)) < 0.4)
    )
    a1, v1, c1 = runtime.step_events(x, cap)
    a2, v2, c2 = runtime.step_events_argsort(x, cap)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_step_events_truncation_keeps_first_capacity():
    x = jnp.asarray([0.0, 1.0, -2.0, 0.0, 3.0, 1.0])
    addrs, values, count = runtime.step_events(x, 2)
    assert int(count) == 2
    np.testing.assert_array_equal(np.asarray(addrs), [1, 2])
    np.testing.assert_array_equal(np.asarray(values), [1.0, -2.0])


def test_step_events_capacity_beyond_fanin_pads():
    x = jnp.asarray([[0.0, 2.0, 0.0, -1.0]])
    addrs, values, count = runtime.step_events(x, 6)
    assert addrs.shape == (1, 6) and int(count[0]) == 2
    np.testing.assert_array_equal(np.asarray(addrs[0]), [1, 3, 0, 0, 0, 0])
    np.testing.assert_array_equal(
        np.asarray(values[0]), [2.0, -1.0, 0, 0, 0, 0]
    )


def test_step_events_empty_plane():
    addrs, values, count = runtime.step_events(jnp.zeros((2, 8)), 4)
    assert not np.asarray(addrs).any()
    assert not np.asarray(values).any()
    assert not np.asarray(count).any()


# ------------------------------------- step_events: TPU sort vs CPU search
_COMPACT = {
    name: jax.jit(fn, static_argnums=1)
    for name, fn in [
        ("sort", runtime._compact_sort),
        ("search", runtime._compact_search),
        ("argsort", runtime.step_events_argsort),
    ]
}


def _plane(kind, shape):
    rng = np.random.default_rng(shape)
    if kind == "binary":
        x = rng.random(shape) < 0.4
    elif kind == "signed":
        x = (rng.random(shape) < 0.4) * rng.choice([-1, 1], shape)
    elif kind == "multi":
        x = (rng.random(shape) < 0.4) * rng.integers(-3, 4, shape)
    elif kind == "zero":
        x = np.zeros(shape)
    else:  # "full": every position active
        x = rng.choice([-2, -1, 1, 2], shape)
    return jnp.asarray(x, jnp.float32)


@pytest.mark.parametrize("kind", ["binary", "signed", "multi", "zero", "full"])
@pytest.mark.parametrize("cap", ["K", "K/2", "7", "K+5"])
@pytest.mark.parametrize("shape", [(25, 4096), (25, 1024), (3, 2, 37)])
def test_step_events_sort_branch_matches_search_bitwise(
    shape, cap, kind, monkeypatch
):
    """The TPU branch (one payload sort), run here on the CPU, is
    bitwise the CPU branch (binary search) and the argsort oracle, also
    through ``encode_step_table``'s int16/int8 packing."""
    K = shape[-1]
    C = {"K": K, "K/2": K // 2, "7": 7, "K+5": K + 5}[cap]
    x = _plane(kind, shape)
    got = [np.asarray(a) for a in _COMPACT["sort"](x, C)]
    # the argsort oracle cannot pad past K; the search branch can
    for ref in ("search", "argsort") if C <= K else ("search",):
        want = [np.asarray(a) for a in _COMPACT[ref](x, C)]
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
    if kind == "full":
        assert (got[2] == min(C, K)).all()

    def encode(x):
        return runtime.encode_step_table(x, C)

    want = jax.jit(encode)(x)
    monkeypatch.setattr(runtime, "step_events", runtime._compact_sort)
    got = jax.jit(encode)(x)
    assert got.addrs.dtype == want.addrs.dtype == jnp.int16
    assert got.values.dtype == want.values.dtype == jnp.int8
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_encode_step_table_lowering_per_platform(platform):
    """Lowered for a TPU, the admission encode at the paper's (25, 4096),
    C=4096 holds neither a ``while`` loop nor a gather, only the sort; the
    CPU lowering keeps the binary search and no sort."""
    x = jax.ShapeDtypeStruct((25, 4096), jnp.float32)
    text = (
        jax.jit(lambda x: runtime.encode_step_table(x, 4096))
        .trace(x)
        .lower(lowering_platforms=(platform,))
        .as_text()
    )
    if platform == "tpu":
        assert "stablehlo.sort" in text
        assert "stablehlo.while" not in text
        assert "gather" not in text
    else:
        assert "stablehlo.while" in text and "gather" in text
        assert "stablehlo.sort" not in text


# --------------------------------------------------------------- autotuner
def test_autotune_capacity_bounds_and_report():
    cfg = snn.SNNConfig(layer_sizes=(64, 24, 2), num_steps=10)
    params = params_for(cfg)
    spikes = _spikes(10, 4, 64, 0.25)
    plan = cap_mod.autotune(
        params, cfg, spikes, percentile=100.0, safety=1.2, align=8
    )
    assert len(plan.capacities) == cfg.num_layers
    for cap, fan_in, mx in zip(plan.capacities, plan.fan_in, plan.max_count):
        assert 1 <= cap <= fan_in
        assert cap % 8 == 0 or cap == fan_in
        assert cap >= min(mx, fan_in)  # p100 + safety: lossless on sample
    assert all(f == 0.0 for f in plan.truncated_lists_frac)
    assert all(f == 0.0 for f in plan.dropped_events_frac)
    report = cap_mod.truncation_report(params, cfg, spikes, plan)
    assert report["pred_agreement"] == 1.0
    assert report["events_dropped_frac"] == 0.0
    assert report["out_mem_max_abs_diff"] < 1e-5


def test_autotune_lossless_plan_preserves_run_chunk_outputs():
    cfg = snn.SNNConfig(layer_sizes=(64, 24, 2), num_steps=8)
    params = params_for(cfg)
    spikes = _spikes(8, 3, 64, 0.2)
    plan = cap_mod.autotune(
        params, cfg, spikes, percentile=100.0, safety=1.5, align=8
    )
    states = _states(cfg, 3)
    _, m_full, p_full, e_full = runtime.run_chunk(
        params, states, spikes, cfg
    )
    _, m_cap, p_cap, e_cap = runtime.run_chunk(
        params, states, spikes, cfg, capacities=plan.capacities
    )
    np.testing.assert_allclose(
        np.asarray(m_cap), np.asarray(m_full), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(p_cap), np.asarray(p_full))
    np.testing.assert_allclose(np.asarray(e_cap), np.asarray(e_full))


def test_aggressive_truncation_reported_honestly():
    cfg = snn.SNNConfig(layer_sizes=(64, 24, 2), num_steps=10)
    params = params_for(cfg)
    spikes = _spikes(10, 4, 64, 0.8)  # busy stream
    plan = cap_mod.autotune(
        params, cfg, spikes, percentile=50.0, safety=1.0, align=8
    )
    assert plan.capacities[0] < plan.max_count[0]
    assert plan.dropped_events_frac[0] > 0.0
    report = cap_mod.truncation_report(params, cfg, spikes, plan)
    assert report["events_dropped_frac"] > 0.0
    assert report["events_truncated"] < report["events_full"]


# ------------------------------------------------------- prepared params
def test_prepare_params_matches_on_the_fly_quant():
    cfg = snn.SNNConfig(layer_sizes=(48, 16, 2), num_steps=6,
                        quant_q115=True)
    params = params_for(cfg)
    spikes = _spikes(6, 2, 48, 0.3)
    states = _states(cfg, 2)
    prepared = runtime.prepare_params(params, cfg)
    _, m_a, p_a, e_a = runtime.run_chunk(params, states, spikes, cfg)
    _, m_b, p_b, e_b = runtime.run_chunk(
        prepared, states, spikes, cfg, prepared=True
    )
    np.testing.assert_array_equal(np.asarray(m_a), np.asarray(m_b))
    np.testing.assert_array_equal(np.asarray(p_a), np.asarray(p_b))
    np.testing.assert_array_equal(np.asarray(e_a), np.asarray(e_b))


@pytest.mark.parametrize("quant", [False, True])
def test_event_eval_forward_matches_bptt_inference(quant):
    """The serving-path eval (event_eval_forward / EventTrainer.evaluate)
    must match the BPTT-graph inference it replaced — including QAT
    configs, where prepare_params must not double-apply."""
    from repro.sparse_train import event_layer

    cfg = snn.SNNConfig(
        layer_sizes=(64, 24, 2), num_steps=8, dropout_rate=0.0,
        quant_q115=quant,
    )
    params = params_for(cfg)
    spikes = _spikes(8, 3, 64, 0.3)
    bm, bs, bev, _ = event_layer.event_bptt_forward(
        params, spikes, cfg, train=False
    )
    em, es, eev = event_layer.event_eval_forward(params, spikes, cfg)
    np.testing.assert_allclose(
        np.asarray(em), np.asarray(bm), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(es), np.asarray(bs))
    np.testing.assert_allclose(np.asarray(eev), np.asarray(bev))
    # prepared params short-circuit: same outputs, no re-quantization
    prepared = runtime.prepare_params(params, cfg)
    pm, ps, pev = event_layer.event_eval_forward(
        prepared, spikes, cfg, prepared=True
    )
    np.testing.assert_array_equal(np.asarray(pm), np.asarray(em))


def test_trainer_evaluate_on_dvs_batch():
    """EventTrainer.evaluate end-to-end on a DVS batch: metrics well-
    formed and predictions consistent with the underlying eval path."""
    from repro.sparse_train import event_layer, trainer

    tcfg = trainer.EventTrainConfig(image_hw=8, num_steps=6, hidden=16)
    t = trainer.EventTrainer(tcfg)
    state = t.init_state(jax.random.PRNGKey(0))
    batch = next(trainer.dvs_batches(0, 4, tcfg))
    ev = t.evaluate(state.params, batch)
    assert 0.0 <= float(ev["accuracy"]) <= 1.0
    assert ev["events_per_layer"].shape == (t.snn_cfg.num_layers,)
    spikes = jnp.moveaxis(batch["spikes"], 0, 1)
    em, es, _ = event_layer.event_eval_forward(
        state.params, spikes, t.snn_cfg
    )
    np.testing.assert_array_equal(
        np.asarray(ev["predictions"]),
        np.asarray(snn.predict_from_traces(em, es)),
    )


def test_engine_backend_knob_jnp_vs_default():
    """The engine's backend/capacities knobs don't change results (auto
    == jnp on CPU; a lossless capacity plan is invisible)."""
    from repro.serving.snn_engine import SNNStreamEngine, StreamRequest

    cfg = snn.SNNConfig(layer_sizes=(64, 24, 2), num_steps=12)
    params = params_for(cfg)
    rng = np.random.default_rng(3)
    trains = [
        (rng.random((12, 64)) < 0.3).astype(np.float32) for _ in range(3)
    ]
    ref = SNNStreamEngine(params, cfg, num_slots=2, chunk_steps=5).run(
        [StreamRequest(spikes=t) for t in trains]
    )
    capped = SNNStreamEngine(
        params, cfg, num_slots=2, chunk_steps=5,
        backend="jnp", capacities=(64, 24),
    ).run([StreamRequest(spikes=t) for t in trains])
    for a, b in zip(ref, capped):
        np.testing.assert_allclose(a.spike_counts, b.spike_counts)
        np.testing.assert_allclose(a.events_per_layer, b.events_per_layer)
        assert a.prediction == b.prediction
