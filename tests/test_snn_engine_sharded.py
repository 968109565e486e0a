"""Mesh-sharded slot axis of the SNN stream engine (subprocess: needs >1
device).  Parity with the unsharded engine over a 2-device CPU mesh, the
loud misconfiguration error for non-divisible slot counts, and elastic
snapshot restore: a snapshot taken on a 2-device slot-sharded engine
warm-restarts a 1-device (unsharded) survivor bit-exactly."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys
    sys.path.insert(0, "src")
    import jax, numpy as np
    from repro.core import snn
    from repro.serving.snn_engine import SNNStreamEngine, StreamRequest

    cfg = snn.SNNConfig(layer_sizes=(64, 24, 2), num_steps=12)
    params = snn.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    trains = [(rng.random((12, 64)) < 0.3).astype(np.float32)
              for _ in range(5)]
    reqs = lambda: [StreamRequest(spikes=t, deadline_s=1e4) for t in trains]
    from repro.launch.mesh import make_host_mesh
    mesh = jax.make_mesh((2,), ("data",))

    ref = SNNStreamEngine(params, cfg, num_slots=2, chunk_steps=5).run(reqs())
    # Explicit axes (jax.make_mesh's default) and Auto axes (the serving
    # CLI's make_host_mesh)
    for m in (mesh, make_host_mesh()):
        eng = SNNStreamEngine(params, cfg, num_slots=2, chunk_steps=5,
                              mesh=m)
        shr = eng.run(reqs())
        # slot arrays keep one sharding from allocation on: one compile
        assert eng.steady_state_recompiles() == 0, m
        for a, b in zip(ref, shr):
            np.testing.assert_allclose(a.spike_counts, b.spike_counts)
            np.testing.assert_allclose(a.events_per_layer,
                                       b.events_per_layer)
            assert a.prediction == b.prediction
            assert not b.deadline_missed

    # slot counts that don't divide over the mesh fail loudly, not silently
    try:
        SNNStreamEngine(params, cfg, num_slots=3, chunk_steps=5, mesh=mesh)
    except ValueError as e:
        assert "num_slots" in str(e)
    else:
        raise AssertionError("non-divisible num_slots did not raise")
    print("SHARDED_SNN_OK")
    """
)


@pytest.mark.slow
def test_sharded_slots_match_unsharded():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=600,
    )
    assert "SHARDED_SNN_OK" in r.stdout, r.stdout + r.stderr


ELASTIC_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys, tempfile
    sys.path.insert(0, "src")
    import jax, numpy as np
    from repro.core import snn
    from repro.serving.snn_engine import SNNStreamEngine, StreamRequest

    cfg = snn.SNNConfig(layer_sizes=(64, 24, 2), num_steps=12)
    params = snn.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    trains = [(rng.random((12, 64)) < 0.3).astype(np.float32)
              for _ in range(5)]
    reqs = lambda: [StreamRequest(spikes=t) for t in trains]
    mesh = jax.make_mesh((2,), ("data",))

    oracle = SNNStreamEngine(params, cfg, num_slots=2,
                             chunk_steps=5).run(reqs())

    # snapshot mid-flight on the 2-device slot-sharded engine ...
    shr = SNNStreamEngine(params, cfg, num_slots=2, chunk_steps=5,
                          mesh=mesh)
    for r in reqs():
        shr.submit(r)
    early = []
    for _ in range(2):
        early.extend(shr.poll())
    snap = os.path.join(tempfile.mkdtemp(), "snap")
    shr.snapshot(snap)

    # ... restore onto a survivor with no mesh (1-device layout)
    surv = SNNStreamEngine(params, cfg, num_slots=2, chunk_steps=5)
    surv.restore(snap)
    got = {r.request_id: r for r in early + surv.drain()}
    assert sorted(got) == [0, 1, 2, 3, 4], sorted(got)
    for ref in oracle:
        r = got[ref.request_id]
        np.testing.assert_array_equal(r.spike_counts, ref.spike_counts)
        np.testing.assert_array_equal(r.events_per_layer,
                                      ref.events_per_layer)
        assert r.prediction == ref.prediction
        assert r.energy_pj == ref.energy_pj
    print("ELASTIC_RESTORE_OK")
    """
)


@pytest.mark.slow
def test_snapshot_from_sharded_restores_onto_single_device():
    """Elastic restore: snapshots are host-resident numpy, so a slot
    snapshot taken on a 2-device mesh warm-restarts an unsharded
    single-device engine with bit-identical results."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", ELASTIC_SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=600,
    )
    assert "ELASTIC_RESTORE_OK" in r.stdout, r.stdout + r.stderr


PREEMPT_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys
    sys.path.insert(0, "src")
    import jax, numpy as np
    from repro.core import snn
    from repro.launch.mesh import make_host_mesh
    from repro.serving.snn_engine import SNNStreamEngine, StreamRequest

    cfg = snn.SNNConfig(layer_sizes=(64, 24, 2), num_steps=12)
    params = snn.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    trains = [(rng.random((12, 64)) < 0.3).astype(np.float32)
              for _ in range(3)]
    mesh = jax.make_mesh((2,), ("data",))

    oracle = SNNStreamEngine(params, cfg, num_slots=2, chunk_steps=5).run(
        [StreamRequest(spikes=t) for t in trains])

    # both slots resident mid-window, then a tighter-deadline arrival
    # parks the loosest one; it resumes later on the sharded layout.
    # Explicit axes, then the serving CLI's Auto-axis make_host_mesh
    for m in (mesh, make_host_mesh()):
        eng = SNNStreamEngine(params, cfg, num_slots=2, chunk_steps=5,
                              mesh=m, preempt=True)
        eng.submit(StreamRequest(spikes=trains[0]))
        eng.submit(StreamRequest(spikes=trains[1], deadline_s=1e4))
        eng.poll()
        eng.submit(StreamRequest(spikes=trains[2], priority=5,
                                 deadline_s=0.5))
        eng.poll()
        assert eng.preempt_parked_depth() == 1
        got = {r.request_id: r for r in eng.drain()}
        snap = eng.metrics.snapshot()
        assert snap["engine.preempt.parked"]["value"] >= 1
        assert snap["engine.preempt.resumed"]["value"] >= 1
        assert eng.steady_state_recompiles() == 0, m
        placed = {d.id for d in eng._ring["addrs"].sharding.device_set}
        assert len(placed) == 2, placed
        assert sorted(got) == [0, 1, 2], sorted(got)
        for ref in oracle:
            r = got[ref.request_id]
            np.testing.assert_array_equal(r.spike_counts, ref.spike_counts)
            np.testing.assert_array_equal(r.events_per_layer,
                                          ref.events_per_layer)
            assert r.prediction == ref.prediction
    print("SHARDED_PREEMPT_OK")
    """
)


def test_sharded_preemption_is_bit_exact():
    """Preemption on a slot-sharded engine: parking a window and resuming
    it write single slot rows on a 2-device mesh with Explicit and with
    Auto axes, with no extra chunk compile, and every window finishes
    bit-identically to an unsharded, unpreempted run."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", PREEMPT_SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=600,
    )
    assert "SHARDED_PREEMPT_OK" in r.stdout, r.stdout + r.stderr
