"""Public kernel API: jit'd wrappers with automatic interpret fallback.

On CPU (this container) every kernel runs in Pallas interpret mode — the
kernel body executes in Python with identical semantics; on a real TPU
backend the same `pl.pallas_call` lowers to Mosaic.  `on_tpu()` picks the
path; callers never pass `interpret` themselves.

Also hosts the composed op the SNN inference path uses:
`snn_layer_step` = spike_matmul -> bias -> lif (the paper's Figure 5
pipeline: cascaded adder -> LIF neuron hardware unit).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels import aer_matmul as _aer
from repro.kernels import lif_fused as _lif
from repro.kernels import q115_matmul as _q115
from repro.kernels import snn_chunk as _chunk
from repro.kernels import spike_matmul as _smm

Array = jax.Array


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def lif_fused(
    currents: Array,
    beta: Array,
    threshold: Array,
    *,
    refractory_steps: int = 0,
    reset: str = "zero",
) -> Tuple[Array, Array]:
    return _lif.lif_fused(
        currents,
        beta,
        threshold,
        refractory_steps=refractory_steps,
        reset=reset,
        interpret=not on_tpu(),
    )


def spike_matmul(spikes: Array, weights_q: Array) -> Array:
    return _smm.spike_matmul(spikes, weights_q, interpret=not on_tpu())


def aer_spike_matmul(addrs: Array, values: Array, weights_q: Array) -> Array:
    """Event-driven synaptic integration over an AER event list.

    out[n] = sum_e values[e] * weights_q[addrs[e], n]  (int32 accumulator,
    the 28-bit-class adder-tree intermediate).  Work scales with the event
    count, not fan-in — the hardware-faithful path for sparse spike trains.
    """
    return _aer.aer_spike_matmul(addrs, values, weights_q,
                                 interpret=not on_tpu())


def aer_spike_matmul_batched(
    addrs: Array, values: Array, weights: Array
) -> Array:
    """Batched event-driven integration, one grid axis per stream.

    out[b, n] = sum_e values[b, e] * weights[addrs[b, e], n] — the
    training-batch analog of ``aer_spike_matmul`` (int16 weights: exact
    int32 accumulation; float32 weights: the surrogate-gradient forward).
    """
    return _aer.aer_spike_matmul_batched(addrs, values, weights,
                                         interpret=not on_tpu())


def snn_chunk(
    weights,
    biases,
    betas,
    thresholds,
    u0,
    r0,
    addrs: Array,
    values: Array,
    counts: Array,
    active: Array,
    *,
    refractory_steps: int = 0,
    reset: str = "zero",
    kind: str = "lif",
    lapicque_gain: float = 1.0,
    interpret=None,
    layout: str = "time_major",
):
    """Fused multi-timestep, multi-layer event-driven SNN chunk.

    One Pallas invocation advances the whole network ``Tc`` steps: layer-0
    weight-row gathers driven by per-slot event lists in SMEM (gated per
    E-block on a non-silent predicate), membranes + refractory counters
    resident in VMEM scratch across all steps, hidden layers as gated
    in-VMEM matvecs.  ``layout="slot_major"`` consumes (B, Tc, C) tables
    (the serving engine's device-resident ring layout) transpose-free.
    See ``kernels.snn_chunk`` for the full contract.
    """
    return _chunk.snn_chunk(
        weights,
        biases,
        betas,
        thresholds,
        u0,
        r0,
        addrs,
        values,
        counts,
        active,
        refractory_steps=refractory_steps,
        reset=reset,
        kind=kind,
        lapicque_gain=lapicque_gain,
        interpret=(not on_tpu()) if interpret is None else interpret,
        layout=layout,
    )


def q115_matmul(x_q: Array, w_q: Array, *, saturate: bool = True) -> Array:
    return _q115.q115_matmul(
        x_q, w_q, saturate=saturate, interpret=not on_tpu()
    )


def snn_layer_forward(
    spikes_T: Array,  # (T, B, fan_in) f32/int {0,1} input spike train
    w: Array,  # (fan_in, fan_out) float weights
    b: Array,  # (fan_out,) float bias
    beta: Array,  # (fan_out,)
    threshold: Array,  # (fan_out,)
    *,
    refractory_steps: int = 0,
) -> Array:
    """Full hardware-path layer: Q1.15 weights, integer cascaded-adder
    integration per step, fused LIF over the window.  Returns spike train
    (T, B, fan_out) f32.

    This is the inference path of paper Fig. 5; training uses the float
    graph in core/snn.py (QAT via quant.fake_quant keeps them aligned).
    """
    T, B, fan_in = spikes_T.shape
    wq = quant.quantize(w, quant.Q1_15)  # (fan_in, fan_out) int16
    bq = quant.quantize(b, quant.Q1_15)  # bias in the same Q1.15 scale

    # integrate all T steps: fold time into rows for one big integration
    spk_i8 = spikes_T.reshape(T * B, fan_in).astype(jnp.int8)
    acc = spike_matmul(spk_i8, wq)  # (T*B, fan_out) int32
    # bias added post-adder-tree in the same fixed-point scale (paper §4.3)
    acc = acc + bq.astype(jnp.int32)[None, :]
    currents = acc.astype(jnp.float32) / quant.Q1_15.scale
    currents = currents.reshape(T, B, -1)

    out_spikes, _ = lif_fused(
        currents, beta, threshold, refractory_steps=refractory_steps
    )
    return out_spikes
