"""Event-driven spike x weight integration — Pallas TPU kernel.

TPU-native analog of the paper's *cascaded adder* (§4.3): activations are
binary spikes, so synaptic integration is a masked add-reduction of weight
rows — no multiplies.  On TPU the energy story shifts from "remove the
multiplier" (MXU multipliers are free silicon) to:

  1. **memory traffic**: spikes travel as int8 (1 byte vs 2/4), weights as
     int16 Q1.15 codes (half of f32);
  2. **event skipping**: spiking activity is sparse (measured ~1-10% in the
     trained net).  Each (m, k) spike tile is reduced on-chip first; a
     whole-tile zero-spike predicate gates the integration arithmetic with
     `pl.when` — silent tiles cost a load + test, not a matmul.  (A deeper
     implementation would gate the weight DMA too via manual copies; noted
     in DESIGN.md.)

Grid: (M/bm, N/bn, K/bk), k innermost ("arbitrary" semantics) accumulating
into an int32 VMEM scratch — the paper's 28-bit adder-tree intermediate.

Integer contract (bit-exact vs ref.spike_matmul_ref):
  acc[m, n] = sum_k spk[m, k] * wq[k, n]   (int32)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _spike_mm_kernel(spk_ref, w_ref, out_ref, acc_scr, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    spk = spk_ref[...]  # (bm, bk) int8 in {0,1}
    n_events = jnp.sum(spk.astype(jnp.int32))

    @pl.when(n_events > 0)
    def _integrate():
        # {0,1} spikes: integer dot == masked add-reduction (adder tree).
        acc_scr[...] += jax.lax.dot_general(
            spk.astype(jnp.int32),
            w_ref[...].astype(jnp.int32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[...] = acc_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def spike_matmul(
    spikes: Array,  # (M, K) int8 {0,1}
    weights_q: Array,  # (K, N) int16 Q1.15 codes
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> Array:
    """Returns int32 accumulator (M, N); dequantize with /2^15."""
    M, K = spikes.shape
    K2, N = weights_q.shape
    assert K == K2, (spikes.shape, weights_q.shape)
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        spikes = jnp.pad(spikes, ((0, pm), (0, pk)))
    if pk or pn:
        weights_q = jnp.pad(weights_q, ((0, pk), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    nk = Kp // bk

    out = pl.pallas_call(
        functools.partial(_spike_mm_kernel, nk=nk),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(spikes, weights_q)
    return out[:M, :N]
