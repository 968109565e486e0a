"""AER event-driven synaptic integration — Pallas TPU kernel.

Where ``spike_matmul`` consumes a *dense* {0,1} spike plane and relies on
whole-tile zero predicates to skip silence, this kernel consumes the AER
event list directly: a vector of active input addresses.  Work is
proportional to the number of events, not the layer fan-in — the true
hardware analog of the paper's event-driven cascaded adder (§4.3), where
only firing synapses clock the adder tree.

Dataflow:
  - event addresses + signed event values ride in as **scalar-prefetch**
    operands (SMEM), available before the body runs so they can drive
    dynamic row indexing;
  - weights are blocked along N only; each grid step owns the full (K, bn)
    column slab in VMEM (Q1.15 int16: 4096 x 128 x 2B = 1 MiB);
  - grid is (N blocks, E blocks), E innermost ("arbitrary"), accumulating
    into an int32 VMEM scratch — the paper's 28-bit-class intermediate;
  - an event-count predicate gates each E block: blocks of pure padding
    (or silent stretches of the stream) cost a scalar test, no gathers.

Integer contract (bit-exact vs ref.aer_spike_matmul_ref):
  out[n] = sum_e values[e] * wq[addrs[e], n]   (int32)

``values`` carries polarity (+1/-1) and padding (0); for the SNN hidden
path it is simply the event-validity mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _aer_kernel(
    addr_ref,  # (E,) int32 scalar-prefetch: event addresses
    val_ref,  # (E,) int32 scalar-prefetch: signed event values (0 = pad)
    w_ref,  # (K, bn) int16 weight column slab
    out_ref,  # (1, bn) int32
    acc_scr,  # (1, bn) int32 VMEM accumulator
    *,
    block_e: int,
    ne: int,
):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    base = e * block_e

    # events in this block (abs: +1/-1 polarities must not cancel the gate)
    def _count(i, c):
        return c + jnp.abs(val_ref[base + i])

    n_events = jax.lax.fori_loop(0, block_e, _count, jnp.int32(0))

    @pl.when(n_events > 0)
    def _integrate():
        def _gather(i, acc):
            a = addr_ref[base + i]
            v = val_ref[base + i]
            row = w_ref[pl.ds(a, 1), :].astype(jnp.int32)  # (1, bn)
            return acc + row * v

        acc_scr[...] = jax.lax.fori_loop(0, block_e, _gather, acc_scr[...])

    @pl.when(e == ne - 1)
    def _flush():
        out_ref[...] = acc_scr[...]


def _aer_batched_kernel(
    addr_ref,  # (B, E) int32 scalar-prefetch: per-stream event addresses
    val_ref,  # (B, E) scalar-prefetch: signed event values (0 = pad)
    w_ref,  # (K, bn) weight column slab (int16 or float32)
    out_ref,  # (1, bn) accumulator dtype
    acc_scr,  # (1, bn) VMEM accumulator
    *,
    block_e: int,
    ne: int,
):
    b = pl.program_id(0)
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    base = e * block_e
    acc_dtype = acc_scr.dtype

    # events of stream b in this E block (nonzero test, not a sum: float
    # magnitudes < 1 must still count, and polarities must not cancel)
    def _count(i, c):
        return c + (val_ref[b, base + i] != 0).astype(jnp.int32)

    n_events = jax.lax.fori_loop(0, block_e, _count, jnp.int32(0))

    @pl.when(n_events > 0)
    def _integrate():
        def _gather(i, acc):
            a = addr_ref[b, base + i]
            v = val_ref[b, base + i].astype(acc_dtype)
            row = w_ref[pl.ds(a, 1), :].astype(acc_dtype)  # (1, bn)
            return acc + row * v

        acc_scr[...] = jax.lax.fori_loop(0, block_e, _gather, acc_scr[...])

    @pl.when(e == ne - 1)
    def _flush():
        out_ref[...] = acc_scr[...]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_e", "interpret")
)
def aer_spike_matmul_batched(
    addrs: Array,  # (B, E) int32 in [0, K); padding slots point anywhere
    values: Array,  # (B, E) int-like / float; 0 on padding
    weights: Array,  # (K, N) int16 Q1.15 codes or float32 weights
    *,
    block_n: int = 128,
    block_e: int = 128,
    interpret: bool = False,
) -> Array:
    """Batched event-driven integration: one grid axis per stream.

    out[b, n] = sum_e values[b, e] * weights[addrs[b, e], n]

    Semantically ``jax.vmap(aer_spike_matmul)`` over the stream axis, but
    as one kernel launch: grid (B, N blocks, E blocks) with the whole
    (B, E) event table scalar-prefetched to SMEM, so every stream's row
    gathers are driven by its own slice.  This is the training-batch path
    (vmap of a scalar-prefetch ``pallas_call`` is not supported on all
    backends, and a single launch amortizes the weight-slab DMA across the
    batch).

    dtype contract: int16 weights accumulate exactly in int32 (bit-exact
    vs ``ref.aer_spike_matmul_ref`` per stream); float32 weights accumulate
    in float32 (the surrogate-gradient training forward).
    """
    B, E = addrs.shape
    K, N = weights.shape
    if weights.dtype == jnp.int16:
        acc_dtype = jnp.int32
        values = values.astype(jnp.int32)
    else:
        acc_dtype = jnp.float32
        weights = weights.astype(jnp.float32)
        values = values.astype(jnp.float32)
    bn = min(block_n, N)
    be = min(block_e, E)
    pe, pn = (-E) % be, (-N) % bn
    if pe:
        addrs = jnp.pad(addrs, ((0, 0), (0, pe)))
        values = jnp.pad(values, ((0, 0), (0, pe)))
    if pn:
        weights = jnp.pad(weights, ((0, 0), (0, pn)))
    Ep, Np = E + pe, N + pn
    ne = Ep // be

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Np // bn, ne),
        in_specs=[
            pl.BlockSpec((K, bn), lambda b, j, e, addr, val: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda b, j, e, addr, val: (b, j)),
        scratch_shapes=[pltpu.VMEM((1, bn), acc_dtype)],
    )
    out = pl.pallas_call(
        functools.partial(_aer_batched_kernel, block_e=be, ne=ne),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Np), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(addrs.astype(jnp.int32), values, weights)
    return out[:, :N]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_e", "interpret")
)
def aer_spike_matmul(
    addrs: Array,  # (E,) int32 in [0, K); padding slots point anywhere
    values: Array,  # (E,) int-like; +1/-1 polarity, 0 on padding
    weights_q: Array,  # (K, N) int16 Q1.15 codes
    *,
    block_n: int = 128,
    block_e: int = 128,
    interpret: bool = False,
) -> Array:
    """Returns int32 accumulator (N,); dequantize with /2^15."""
    (E,) = addrs.shape
    K, N = weights_q.shape
    bn = min(block_n, N)
    be = min(block_e, E)
    pe, pn = (-E) % be, (-N) % bn
    if pe:
        addrs = jnp.pad(addrs, (0, pe))
        values = jnp.pad(values, (0, pe))
    if pn:
        weights_q = jnp.pad(weights_q, ((0, 0), (0, pn)))
    Ep, Np = E + pe, N + pn
    ne = Ep // be

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Np // bn, ne),
        in_specs=[
            pl.BlockSpec((K, bn), lambda j, e, addr, val: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda j, e, addr, val: (0, j)),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_aer_kernel, block_e=be, ne=ne),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(addrs.astype(jnp.int32), values.astype(jnp.int32), weights_q)
    return out[0, :N]
