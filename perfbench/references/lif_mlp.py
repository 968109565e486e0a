"""Plain reference of the paper's LIF MLP (arXiv 2411.01628, Eq. 2).

Dense float32 layers, one time step after another, in ``jax.numpy``; it
imports nothing of the program.  Per step and layer:

    u_pre = beta * u + (h @ w + b),  spike = (u_pre >= threshold),
    u     = u_pre - u_pre * spike     (reset to zero)

with ``beta = sigmoid(beta_raw)`` (the learnable pre-sigmoid parameter, as
the paper's snntorch model stores it).  The prediction is the arg-max of
the output spike counts, a tie broken by the output membrane (after
reset) summed over the window: the classifier's decision variable, which
the comparison reads as a model's logits are read.  The tie is broken on
the sums themselves: added to the counts at 1e-6, as the program adds
them in float64, float32 would round the sums' difference away.

``precision`` is the matmul precision of the configuration, "highest"
(float32).  "high" is the control: every weight rounded to what three
bfloat16 passes keep of it (a high and a low bfloat16 part), computed the
same way on every platform.  Inputs and hidden activations are spikes in
{0, 1}, which bfloat16 holds exactly, so this is the product the chip's
three-pass mode computes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def make_params(cfg: dict, key: jax.Array) -> dict:
    """Random weights from ``key`` on the device, in one jitted call:
    Kaiming-uniform layers, the output layer's weights and bias scaled by
    ``out_gain`` so that its neurons fire, beta and threshold as the
    configuration states."""
    sizes = tuple(cfg["layer_sizes"])
    beta_raw = float(np.log(cfg["beta"] / (1.0 - cfg["beta"])))

    @jax.jit
    def init(key):
        params = {}
        keys = jax.random.split(key, len(sizes) - 1)
        for i, (fi, fo) in enumerate(zip(sizes[:-1], sizes[1:])):
            bound = 1.0 / np.sqrt(fi)
            gain = cfg["out_gain"] if i == len(sizes) - 2 else 1.0
            wk, bk = jax.random.split(keys[i])
            params[f"layer{i}"] = {
                "w": gain * jax.random.uniform(
                    wk, (fi, fo), minval=-bound, maxval=bound),
                "b": gain * jax.random.uniform(
                    bk, (fo,), minval=-bound, maxval=bound),
                "beta_raw": jnp.full((fo,), beta_raw, jnp.float32),
                "threshold": jnp.full((fo,), cfg["threshold"], jnp.float32),
            }
        return params

    return init(key)


def _to_bf16(x):
    """``x`` rounded to the nearest bfloat16 (ties to even), kept in
    float32.  Done on the bits: a float32 -> bfloat16 -> float32 convert
    pair is one that XLA may drop as excess precision."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    odd = (bits >> 16) & jnp.uint32(1)
    bits = (bits + jnp.uint32(0x7FFF) + odd) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _three_pass(w):
    hi = _to_bf16(w)
    return hi + _to_bf16(w - hi)


@functools.partial(jax.jit, static_argnames=("precision",))
def forward(params: dict, spikes, precision: str = "highest"):
    """``spikes`` (T, B, K) -> per request: hidden spike totals (B,) of
    the first layer, output spike counts (B, n_out), the output membrane
    summed over the window (B, n_out), prediction (B,)."""
    layers = [params[f"layer{i}"] for i in range(len(params))]
    if precision == "high":
        layers = [{**lp, "w": _three_pass(lp["w"])} for lp in layers]
    elif precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    B = spikes.shape[1]

    def step(us, x):
        h = x
        new, spikes_out = [], []
        for lp, u in zip(layers, us):
            cur = jnp.matmul(h, lp["w"], precision="highest") + lp["b"]
            u_pre = jax.nn.sigmoid(lp["beta_raw"]) * u + cur
            spk = (u_pre - lp["threshold"] >= 0.0).astype(jnp.float32)
            u = u_pre - u_pre * spk
            new.append(u)
            spikes_out.append(spk)
            h = spk
        return new, (spikes_out[0].sum(-1), spikes_out[-1], new[-1])

    us0 = [jnp.zeros((B, lp["w"].shape[1]), jnp.float32) for lp in layers]
    _, (hid, out, mem) = jax.lax.scan(step, us0, spikes)
    counts, memsum = out.sum(0), mem.sum(0)
    return hid.sum(0), counts, memsum, predict(counts, memsum)


def predict(counts, memsum):
    """Arg-max of the spike counts, a tie broken by the membrane sums."""
    top = counts == counts.max(-1, keepdims=True)
    return jnp.argmax(jnp.where(top, memsum, -jnp.inf), axis=-1)
