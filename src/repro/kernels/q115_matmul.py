"""Q1.15 fixed-point matmul — Pallas TPU kernel (paper §4.3 number format).

int16 Q1.15 x int16 Q1.15 with the FPGA's dataflow: each product is
rescaled back to Q1.15 (>>15, round-to-nearest) *before* accumulation so a
fan-in-4096 sum fits the paper's 28-bit intermediate (16 + log2(4096));
the int32 VMEM accumulator plays that role.  Output saturates to int16.

The product tensor (bm, bk, bn) is materialized per k-slab, so block_k is
kept small (16) to bound VMEM: 128*16*128 * 4B = 1 MiB.

Bit-exact contract vs ref.q115_matmul_ref / q115_matmul_acc_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

FRAC_BITS = 15
_ROUND = 1 << (FRAC_BITS - 1)


def _q115_kernel(x_ref, w_ref, out_ref, acc_scr, *, nk: int, saturate: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.int32)  # (bm, bk)
    w = w_ref[...].astype(jnp.int32)  # (bk, bn)
    # Q1.15*Q1.15 -> Q2.30 products, rescale each to Q1.15 pre-accumulate
    prod = x[:, :, None] * w[None, :, :]  # (bm, bk, bn) int32, <= 2^30
    prod = (prod + _ROUND) >> FRAC_BITS
    acc_scr[...] += jnp.sum(prod, axis=1)

    @pl.when(k == nk - 1)
    def _flush():
        acc = acc_scr[...]
        if saturate:
            out_ref[...] = jnp.clip(acc, -(2**15), 2**15 - 1).astype(
                jnp.int16
            )
        else:
            out_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("saturate", "block_m", "block_n", "block_k", "interpret"),
)
def q115_matmul(
    x_q: Array,  # (M, K) int16 Q1.15
    w_q: Array,  # (K, N) int16 Q1.15
    *,
    saturate: bool = True,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 16,
    interpret: bool = False,
) -> Array:
    """Q1.15 matmul.  saturate=True -> int16 Q1.15 out; else raw int32."""
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x_q = jnp.pad(x_q, ((0, pm), (0, pk)))
    if pk or pn:
        w_q = jnp.pad(w_q, ((0, pk), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    nk = Kp // bk
    out_dtype = jnp.int16 if saturate else jnp.int32

    out = pl.pallas_call(
        functools.partial(_q115_kernel, nk=nk, saturate=saturate),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_q, w_q)
    return out[:M, :N]
