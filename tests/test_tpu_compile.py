"""Compile the serving path for a described TPU v5e chip, without the chip.

The TPU compiler is installed even where no TPU is attached: it compiles
for a topology that is only described, and refuses what the chip's
compiler would refuse (a block that breaks Mosaic's tiling rule, more
scalar memory than a kernel may use).  Interpret-mode parity tests cannot
see either.  Nothing here runs; these tests prove only that Mosaic
accepts the fused chunk kernel at the paper's full width.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.collision_snn import CONFIG
from repro.core import snn
from repro.kernels import ops
from repro.kernels.snn_chunk import snn_chunk
from repro.serving.snn_engine import SNNStreamEngine

TC = 5  # the engine's default chunk_steps


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    # keep the TPU compiler's logs out of the shared /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


CONFIG_128PX = dataclasses.replace(CONFIG, layer_sizes=(16384, 512, 2))


@pytest.mark.parametrize("sizes,slots,cap", [
    (CONFIG.layer_sizes, 4, 1152),
    (CONFIG.layer_sizes, 8, 4096),
    (CONFIG.layer_sizes, 32, 1152),
    # 128 px: event rows a step at a time, a 32 MiB slab under the
    # planner's scoped VMEM limit
    (CONFIG_128PX.layer_sizes, 8, 16384),
], ids=["4-1152", "8-4096", "32-1152", "128px-8-16384"])
def test_snn_chunk_compiles_for_v5e(
    one_chip, no_compile_cache, sizes, slots, cap
):

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n = sizes[1:]
    args = (
        tuple(spec((sizes[i], n[i]), jnp.float32) for i in range(len(n))),
        tuple(spec((k,), jnp.float32) for k in n),  # biases
        tuple(spec((k,), jnp.float32) for k in n),  # betas
        tuple(spec((k,), jnp.float32) for k in n),  # thresholds
        tuple(spec((slots, k), jnp.float32) for k in n),  # membranes
        tuple(spec((slots, k), jnp.int32) for k in n),  # refractory
        spec((slots, TC, cap), jnp.int16),  # ring addresses
        spec((slots, TC, cap), jnp.int8),  # ring values
        spec((slots, TC), jnp.int32),  # counts
        spec((slots,), jnp.int32),  # active
    )
    compiled = (
        jax.jit(lambda *a: snn_chunk(*a, layout="slot_major"))
        .lower(*args)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_chunk_compiles_for_v5e(
    one_chip, no_compile_cache, monkeypatch
):
    """The engine's whole jitted tick chunk at its defaults (8 slots,
    Tc=5, C=4096), as ``backend="auto"`` resolves it on a TPU."""
    _engine_chunk_compiles(CONFIG, one_chip, monkeypatch)


def test_engine_chunk_compiles_for_v5e_at_128px(
    one_chip, no_compile_cache, monkeypatch
):
    """The same at the paper's 128 px input, 16384-512-2 (C=16384)."""
    _engine_chunk_compiles(CONFIG_128PX, one_chip, monkeypatch)


def _engine_chunk_compiles(cfg, one_chip, monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    engine = SNNStreamEngine(
        snn.init_params(jax.random.PRNGKey(0), cfg), cfg
    )
    K = cfg.layer_sizes[0]
    assert (engine.backend, engine.S, engine.Tc, engine.C) == (
        "fused", 8, TC, K,
    )
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (engine._prepared, engine._states, engine._ring, engine._meta),
    )
    compiled = engine._chunk.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_admission_compiles_for_v5e(one_chip, no_compile_cache):
    """The engine's admission program at its defaults (a (25, 4096)
    train, C=4096) compiles for a TPU to the payload sort, with neither
    the binary search's ``while`` loop nor a gather."""
    engine = SNNStreamEngine(
        snn.init_params(jax.random.PRNGKey(0), CONFIG), CONFIG
    )
    assert engine.C == 4096

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    args = jax.tree.map(spec, (engine._ring, engine._meta)) + (
        spec(jax.ShapeDtypeStruct((CONFIG.num_steps, 4096), jnp.float32)),
        spec(jax.ShapeDtypeStruct((), jnp.int32)),
    )
    text = engine._admit_spikes_fn.lower(*args).compile().as_text()
    assert " sort(" in text
    assert " while(" not in text and " gather(" not in text
