"""Event-driven SNN forward pass over AER events.

``core.snn.forward`` computes every layer densely: each step multiplies the
full (fan_in, fan_out) weight matrix regardless of how few inputs spiked.
This runtime implements the paper's actual dataflow: each step extracts the
*events* (active input addresses) and gathers only those weight rows into
the accumulation — work scales with measured spiking activity.

Float semantics match ``core.snn.forward`` (inference mode) up to
accumulation-order rounding: a gathered sum adds the same weight rows a
dense matmul does, in a different order, so outputs agree to float32
tolerance (property-tested on the paper's 4096-512-2 collision config).
The neuron update reuses ``core.neuron.neuron_step`` verbatim.

Every entry point also *measures* per-layer event counts, which feed
``core.energy.snn_ops_from_events`` — replacing the repo's assumed
spike-rate energy model with counted events (the ISSUE's "measured, not
assumed" energy accounting).

State is explicit (``init_states`` / ``run_chunk``) so the streaming
serving engine can carry membrane potentials across request chunks.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import neuron, quant, snn
from repro.events import aer

Array = jax.Array


# --------------------------------------------------------------------------
# Per-step event extraction + gathered synaptic integration
# --------------------------------------------------------------------------


def step_events(x: Array, capacity: int) -> Tuple[Array, Array, Array]:
    """Extract the event list of one spike plane ``x`` (..., K).

    Returns (addrs (..., C) int32, values (..., C) float32, count (...,)
    int32); ``values`` carries the (signed) spike magnitude, 0 on padding.
    Addresses are the active positions in ascending order; at
    ``capacity`` the list truncates to the *first* ``capacity`` of them,
    and past ``count`` both lists are 0.

    The compaction is lowered per platform, and both branches return
    bitwise identical arrays (tests/test_snn_chunk.py):

    - CPU and every other platform but the TPU (``_compact_search``): a
      cumsum gives each active position its output slot, and the inverse
      map (which position feeds slot c) is a vectorized binary search
      plus one gather.  Measured on the CPU ~15-25x faster than the
      argsort (benchmarks/snn_bench.py) and about 6x faster than the
      sort below.
    - TPU (``_compact_sort``): one sort along K of unique keys (the
      address, offset by K where the position is silent) carrying ``x``
      as payload, so neither a ``while`` loop nor a gather remains.  On a
      TPU v5e each gathered element costs about 10 ns: the search's
      ``while`` took log2 K + 1 times the final values gather (13.0x at
      K=4096, 11.0x at K=1024), 14.7 ms per (25, 4096) admission in the
      benchmark's device trace, while the sort is a native vectorized
      network.
    """
    return jax.lax.platform_dependent(
        x,
        tpu=functools.partial(_compact_sort, capacity=capacity),
        default=functools.partial(_compact_search, capacity=capacity),
    )


def _compact_search(x: Array, capacity: int) -> Tuple[Array, Array, Array]:
    """``step_events`` by cumsum ranks and a binary search: O(K + C log K)."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    active = x != 0
    pos = jnp.cumsum(active.astype(jnp.int32), axis=-1)  # 1-indexed rank
    count = jnp.minimum(pos[..., -1], capacity).astype(jnp.int32)
    R = int(np.prod(lead)) if lead else 1
    # src[c] = first position whose rank reaches c+1 (stable: ascending
    # address order), found by binary search over the monotone ranks
    targets = jnp.arange(1, capacity + 1, dtype=jnp.int32)
    src = jax.vmap(
        lambda p: jnp.searchsorted(p, targets, side="left")
    )(pos.reshape(R, K))
    src = jnp.minimum(src, K - 1).astype(jnp.int32).reshape(*lead, capacity)
    valid = jnp.arange(capacity, dtype=jnp.int32) < count[..., None]
    addrs = jnp.where(valid, src, 0)
    values = jnp.where(valid, jnp.take_along_axis(x, src, axis=-1), 0.0)
    return addrs, values.astype(jnp.float32), count


def _compact_sort(x: Array, capacity: int) -> Tuple[Array, Array, Array]:
    """``step_events`` by one payload sort: no loop, no gather."""
    K = x.shape[-1]
    axis = x.ndim - 1
    active = x != 0
    count = jnp.minimum(
        jnp.sum(active, axis=-1, dtype=jnp.int32), capacity
    ).astype(jnp.int32)
    # unique keys: active positions first, each group in address order,
    # so the sorted keys' head is the addresses and no stable sort (which
    # compiles about 3x slower for a TPU) is needed
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    src, vals = jax.lax.sort(
        (jnp.where(active, iota, iota + K), x),
        dimension=axis,
        is_stable=False,
        num_keys=1,
    )
    src, vals = src[..., :capacity], vals[..., :capacity]
    if capacity > K:
        pad = [(0, 0)] * axis + [(0, capacity - K)]
        src, vals = jnp.pad(src, pad), jnp.pad(vals, pad)
    valid = jnp.arange(capacity, dtype=jnp.int32) < count[..., None]
    addrs = jnp.where(valid, src, 0)
    values = jnp.where(valid, vals, 0.0)
    return addrs, values.astype(jnp.float32), count


def step_events_argsort(x: Array, capacity: int) -> Tuple[Array, Array, Array]:
    """Original argsort-compaction event extraction (O(K log K)).

    Kept as the oracle for ``step_events`` and as the baseline in
    ``benchmarks/snn_bench.py``; ``step_events`` is the path every caller
    runs.
    """
    active = x != 0
    order = jnp.argsort(~active, axis=-1, stable=True)[..., :capacity]
    count = jnp.minimum(jnp.sum(active, axis=-1), capacity).astype(jnp.int32)
    valid = jnp.arange(capacity, dtype=jnp.int32) < count[..., None]
    addrs = jnp.where(valid, order, 0).astype(jnp.int32)
    values = jnp.where(valid, jnp.take_along_axis(x, order, axis=-1), 0.0)
    return addrs, values.astype(jnp.float32), count


def gather_current(
    w: Array,  # (K, N) float weights
    b: Array,  # (N,) float bias
    addrs: Array,  # (B, C) int32 event addresses
    values: Array,  # (B, C) float event values (0 = padding)
    *,
    chunk: int = 256,
) -> Array:
    """Event-driven synaptic integration: sum of gathered weight rows.

    Processes events in fixed chunks so peak memory is (B, chunk, N)
    regardless of capacity — the jnp mirror of the Pallas
    ``aer_spike_matmul`` E-block loop.
    """
    B, C = addrs.shape
    pad = (-C) % chunk
    if pad:
        addrs = jnp.pad(addrs, ((0, 0), (0, pad)))
        values = jnp.pad(values, ((0, 0), (0, pad)))
    nc = (C + pad) // chunk
    a_chunks = addrs.reshape(B, nc, chunk).transpose(1, 0, 2)
    v_chunks = values.reshape(B, nc, chunk).transpose(1, 0, 2)

    def body(acc, xs):
        a_c, v_c = xs  # (B, chunk)
        rows = jnp.take(w, a_c, axis=0)  # (B, chunk, N)
        return acc + jnp.einsum("bc,bcn->bn", v_c, rows), None

    acc0 = jnp.zeros((B, w.shape[1]), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (a_chunks, v_chunks))
    return acc + b[None, :]


def encode_step_table(
    spikes: Array,  # (..., T, K) dense spike train, integer-valued
    capacity: int,
    *,
    addr_dtype=None,
) -> aer.StepEventTable:
    """Compress a dense spike train into a packed per-step event table.

    One ``step_events`` pass over every step at once (the extraction is
    per-step independent, so slicing the table at step ``d`` is bitwise
    identical to extracting ``spikes[d]`` on the fly — the property the
    serving engine's ring-buffer residency rests on).  Values are stored
    as int8 signed magnitudes: spike trains are integer-valued by
    construction, and the engine validates that at submit.
    """
    addrs, values, counts = step_events(spikes, capacity)
    if addr_dtype is None:
        addr_dtype = aer.addr_dtype_for(spikes.shape[-1])
    # a layer wider than the dtype's range would silently wrap addresses
    # negative at astype(); fail at trace time instead
    aer.check_addr_dtype(spikes.shape[-1], addr_dtype)
    return aer.StepEventTable(
        addrs=addrs.astype(addr_dtype),
        values=values.astype(jnp.int8),
        counts=counts.astype(jnp.int32),
    )


# --------------------------------------------------------------------------
# Stateful chunk runner (shared by event_forward and the serving engine)
# --------------------------------------------------------------------------


def init_states(cfg: snn.SNNConfig, batch: int) -> List[neuron.NeuronState]:
    return [
        neuron.init_state((batch, cfg.layer_sizes[i + 1]))
        for i in range(cfg.num_layers)
    ]


def prepare_params(params, cfg: snn.SNNConfig):
    """One-time parameter preparation for the chunk runtime.

    Applies the config's Q1.15 fake-quantization (a no-op otherwise).  Do
    this once at engine/trainer init and pass ``prepared=True`` to
    ``run_chunk`` — the original hot loop re-quantized the full weight set
    on every chunk execution.
    """
    if not cfg.quant_q115:
        return params
    return {
        name: {
            **lp,
            "w": quant.fake_quant(lp["w"], quant.Q1_15),
            "b": quant.fake_quant(lp["b"], quant.Q1_15),
        }
        for name, lp in params.items()
    }


# backward-compatible alias (pre-overhaul name)
_maybe_quant = prepare_params


def run_chunk(
    params: Dict[str, Dict[str, Array]],
    states: List[neuron.NeuronState],
    spikes: Array,  # (Tc, B, K) input spike planes for this chunk
    cfg: snn.SNNConfig,
    *,
    active: Optional[Array] = None,  # (B,) mask; inactive rows are frozen
    capacities: Optional[Sequence[int]] = None,  # per-layer event caps
    prepared: bool = False,  # params already through prepare_params
    backend: str = "jnp",  # "jnp" | "fused" | "auto"
    interpret: Optional[bool] = None,  # fused path: force interpret mode
) -> Tuple[List[neuron.NeuronState], Array, Array, Array]:
    """Advance the network ``Tc`` steps event-drivenly.

    Returns (new_states, out_mem (Tc, B, C), out_spikes (Tc, B, C),
    events (Tc, n_layers, B) — measured input-event count per layer and
    step, so callers can attribute events to requests that finish
    mid-chunk).

    ``active`` freezes finished batch slots: their inputs are silenced and
    their membrane state is held, so one compiled chunk serves a partially
    filled micro-batch (continuous batching).

    ``capacities`` bounds each layer's per-step event list (default: full
    fan-in, no truncation).  Tuned capacities (``events.capacity``) shrink
    the gather loop to the measured activity envelope.

    ``backend`` selects the hot path: ``"jnp"`` is the scan-of-gathers
    oracle, ``"fused"`` the single-invocation Pallas chunk kernel
    (``kernels.snn_chunk``), and ``"auto"`` picks fused on TPU and jnp on
    CPU (where the fused kernel would run interpreted).  The fused path
    applies ``capacities[0]`` to the input event list; hidden layers run
    as gated in-VMEM matvecs and never truncate.

    Layer-0 events are extracted *once* for the whole chunk (vectorized
    over steps — ``step_events`` is per-step independent) and handed to
    ``run_chunk_events``; callers that already hold packed event tables
    (the device-resident serving engine) skip this entry point entirely.
    """
    B = spikes.shape[1]
    p = params if prepared else prepare_params(params, cfg)
    act = (
        jnp.ones((B,), jnp.float32)
        if active is None
        else active.astype(jnp.float32)
    )
    caps = _resolve_capacities(cfg, capacities)
    # silence frozen slots before extraction so their (ignored) event
    # tables cost nothing downstream and counts match across backends
    addrs, values, counts = step_events(
        spikes * act[None, :, None], caps[0]
    )
    return run_chunk_events(
        p,
        states,
        addrs,
        values,
        counts,
        cfg,
        active=act,
        capacities=caps,
        prepared=True,
        backend=backend,
        interpret=interpret,
    )


def run_chunk_events(
    params: Dict[str, Dict[str, Array]],
    states: List[neuron.NeuronState],
    addrs: Array,  # (Tc, B, C) int — layer-0 event addresses, valid-first
    values: Array,  # (Tc, B, C) — signed event values (0 = padding)
    counts: Array,  # (Tc, B) int — valid events per step
    cfg: snn.SNNConfig,
    *,
    active: Optional[Array] = None,  # (B,) mask; inactive rows are frozen
    capacities: Optional[Sequence[int]] = None,
    prepared: bool = False,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
    layout: str = "time_major",  # "time_major" (Tc,B,C) | "slot_major" (B,Tc,C)
) -> Tuple[List[neuron.NeuronState], Array, Array, Array]:
    """``run_chunk`` over a *pre-extracted* layer-0 event table.

    The serving hot path: the engine stages each request's events in a
    device-resident ring at admission and slices the next ``Tc`` steps per
    chunk — this entry consumes those slices directly instead of
    re-running ``step_events`` on a dense layer-0 plane every chunk.
    Event lists must be packed valid-first with zero values on padding
    (what ``step_events``/``encode_step_table`` produce), already
    truncated to ``capacities[0]``, and silenced (zero values/counts) on
    frozen or out-of-window steps.  ``layout="slot_major"`` accepts the
    ring's native (B, Tc, C) layout without a host-side transpose.

    Returns the ``run_chunk`` tuple: (new_states, out_mem, out_spikes,
    events (Tc, n_layers, B)).
    """
    ncfg = cfg.neuron_cfg
    p = params if prepared else prepare_params(params, cfg)
    n_layers = cfg.num_layers
    if layout == "slot_major":
        B = addrs.shape[0]
    elif layout == "time_major":
        B = addrs.shape[1]
    else:
        raise ValueError(f"unknown event layout {layout!r}")
    act = (
        jnp.ones((B,), jnp.float32)
        if active is None
        else active.astype(jnp.float32)
    )
    caps = _resolve_capacities(cfg, capacities)

    backend = resolve_backend(backend)
    if backend == "fused":
        return _run_chunk_fused(
            p, states, addrs, values, counts, cfg, act, caps, interpret,
            layout=layout,
        )
    if backend != "jnp":
        raise ValueError(f"unknown run_chunk backend {backend!r}")

    if layout == "slot_major":
        addrs = jnp.swapaxes(addrs, 0, 1)
        values = jnp.swapaxes(values, 0, 1)
        counts = jnp.swapaxes(counts, 0, 1)

    def step(states, xs):
        a_t, v_t, c_t = xs
        new_states, ev_t = [], []
        h = None
        for i in range(n_layers):
            lp = p[f"layer{i}"]
            if i == 0:
                cur = gather_current(
                    lp["w"], lp["b"], a_t.astype(jnp.int32),
                    v_t.astype(jnp.float32),
                )
                count = c_t.astype(jnp.float32)
            else:
                a_i, v_i, c_i = step_events(h, caps[i])
                cur = gather_current(lp["w"], lp["b"], a_i, v_i)
                count = c_i.astype(jnp.float32)
            st, spk = neuron.neuron_step(
                ncfg,
                states[i],
                cur,
                beta=snn.effective_beta(lp),
                threshold=lp["threshold"],
            )
            # frozen slots keep their previous membrane/refractory state
            st = neuron.NeuronState(
                u=jnp.where(act[:, None] > 0, st.u, states[i].u),
                refrac=jnp.where(
                    act[:, None] > 0, st.refrac, states[i].refrac
                ),
            )
            spk = spk * act[:, None]
            new_states.append(st)
            ev_t.append(count)
            h = spk
        out_mem_t = new_states[-1].u
        return tuple(new_states), (out_mem_t, h, jnp.stack(ev_t))

    fin_states, (out_mem, out_spikes, events) = jax.lax.scan(
        step, tuple(states), (addrs, values, counts)
    )
    return list(fin_states), out_mem, out_spikes, events


def resolve_backend(backend: str) -> str:
    """The chunk backend ``backend`` names: ``"auto"`` is ``"fused"`` on
    TPU and ``"jnp"`` elsewhere (where the fused kernel would run
    interpreted); any other name is returned as given."""
    if backend != "auto":
        return backend
    from repro.kernels import ops as _ops

    return "fused" if _ops.on_tpu() else "jnp"


def _resolve_capacities(
    cfg: snn.SNNConfig, capacities: Optional[Sequence[int]]
) -> List[int]:
    if capacities is None:
        return [int(cfg.layer_sizes[i]) for i in range(cfg.num_layers)]
    caps = [int(c) for c in capacities]
    if len(caps) != cfg.num_layers:
        raise ValueError(
            f"capacities has {len(caps)} entries for {cfg.num_layers} layers"
        )
    if any(c < 1 for c in caps):
        raise ValueError(f"capacities must be >= 1, got {caps}")
    return caps


def _run_chunk_fused(
    p, states, addrs, values, counts, cfg: snn.SNNConfig, act, caps,
    interpret, *, layout: str = "time_major",
):
    """Dispatch one chunk to the fused Pallas kernel.

    The kernel reads packed valid-first event tables from SMEM —
    exactly the staged format, so no extraction happens here.
    """
    from repro.kernels import ops

    ncfg = cfg.neuron_cfg
    L = cfg.num_layers
    # the fused kernel truncates only the input event list (capacities[0]);
    # hidden layers run as dense in-VMEM matvecs.  A truncating hidden
    # capacity would make fused and jnp return different outputs for the
    # same arguments — and backend="auto" platform-dependent — so reject
    # it loudly instead of diverging silently.
    for i in range(1, L):
        if caps[i] < cfg.layer_sizes[i]:
            raise ValueError(
                f"backend='fused' cannot truncate hidden layers: "
                f"capacities[{i}]={caps[i]} < fan-in {cfg.layer_sizes[i]}. "
                f"Use full fan-in hidden capacities (autotune(..., "
                f"tune_hidden=False)) or backend='jnp'."
            )
    layers = [p[f"layer{i}"] for i in range(L)]
    mem, spk, events, u_fin, r_fin = ops.snn_chunk(
        tuple(lp["w"] for lp in layers),
        tuple(lp["b"] for lp in layers),
        tuple(snn.effective_beta(lp) for lp in layers),
        tuple(lp["threshold"] for lp in layers),
        tuple(st.u for st in states),
        tuple(st.refrac for st in states),
        addrs,
        values,
        counts,
        act,
        refractory_steps=ncfg.refractory_steps,
        reset=ncfg.reset,
        kind=ncfg.kind,
        lapicque_gain=ncfg.lapicque_gain,
        interpret=interpret,
        layout=layout,
    )
    new_states = [
        neuron.NeuronState(u=u, refrac=r) for u, r in zip(u_fin, r_fin)
    ]
    return new_states, mem, spk, events


# --------------------------------------------------------------------------
# Whole-window forward passes
# --------------------------------------------------------------------------


def event_forward(
    params: Dict[str, Dict[str, Array]],
    spikes: Array,  # (T, B, K) in {0,1}
    cfg: snn.SNNConfig,
    *,
    capacities: Optional[Sequence[int]] = None,
    prepared: bool = False,
    backend: str = "jnp",
) -> Tuple[Array, Array, Array]:
    """Event-driven analog of ``core.snn.forward`` (inference mode).

    Returns (out_mem (T,B,C), out_spikes (T,B,C), events (n_layers, B)).
    Outputs match the dense forward to float32 tolerance; ``events`` are
    the *measured* per-layer input-event counts of this window.
    """
    states = init_states(cfg, spikes.shape[1])
    _, out_mem, out_spikes, events = run_chunk(
        params,
        states,
        spikes,
        cfg,
        capacities=capacities,
        prepared=prepared,
        backend=backend,
    )
    return out_mem, out_spikes, jnp.sum(events, axis=0)


def event_forward_aer(
    params: Dict[str, Dict[str, Array]],
    stream: aer.EventStream,  # batch dims (B,), addresses over layer_sizes[0]
    cfg: snn.SNNConfig,
    *,
    num_steps: Optional[int] = None,
) -> Tuple[Array, Array, Array]:
    """Run the SNN directly on an AER input stream (e.g. DVS events).

    The input layer never materializes a dense plane: each step's events
    are sliced out of the time-sorted stream and gathered straight into
    the synaptic integration (polarity-signed).  Hidden layers proceed as
    in ``event_forward``.
    """
    T = num_steps if num_steps is not None else cfg.num_steps
    ncfg = cfg.neuron_cfg
    p = prepare_params(params, cfg)
    n_layers = cfg.num_layers
    B, E = stream.times.shape

    # per-row event ranges of every step: boundaries (B, T+1)
    steps = jnp.arange(T + 1, dtype=jnp.int32)
    boundaries = jax.vmap(
        lambda tr: jnp.searchsorted(tr, steps, side="left")
    )(stream.times).astype(jnp.int32)

    states = init_states(cfg, B)
    offs = jnp.arange(E, dtype=jnp.int32)

    def step(carry, t):
        states, ev = carry
        start, end = boundaries[:, t], boundaries[:, t + 1]
        # mask by polarity != 0 on top of the window: padding slots carry
        # polarity 0, and while canonical pads sit at time
        # num_steps_at_encode (outside every window), merge() without
        # num_steps stamps pads at max(times)+1 — which for a stream
        # shorter than T lands *inside* [0, T).  An end-start count would
        # then bill padding as events, inflating measured events/energy.
        valid = (
            (offs[None, :] >= start[:, None])
            & (offs[None, :] < end[:, None])
            & (stream.polarity != 0)
        )
        addrs = jnp.where(valid, stream.addrs, 0)
        values = jnp.where(valid, stream.polarity.astype(jnp.float32), 0.0)
        new_states, new_ev = [], []
        lp = p["layer0"]
        cur = gather_current(lp["w"], lp["b"], addrs, values)
        count = jnp.sum(valid, axis=-1).astype(jnp.float32)
        h = None
        for i in range(n_layers):
            lp = p[f"layer{i}"]
            if i > 0:
                addrs, vals, cnt = step_events(h, cfg.layer_sizes[i])
                cur = gather_current(lp["w"], lp["b"], addrs, vals)
                count = cnt.astype(jnp.float32)
            st, spk = neuron.neuron_step(
                ncfg,
                states[i],
                cur,
                beta=snn.effective_beta(lp),
                threshold=lp["threshold"],
            )
            new_states.append(st)
            new_ev.append(ev[i] + count)
            h = spk
        return (tuple(new_states), tuple(new_ev)), (new_states[-1].u, h)

    ev0 = tuple(jnp.zeros((B,), jnp.float32) for _ in range(n_layers))
    (_, fin_ev), (out_mem, out_spikes) = jax.lax.scan(
        step, (tuple(states), ev0), jnp.arange(T)
    )
    return out_mem, out_spikes, jnp.stack(fin_ev)


def predict_events(
    params, spikes: Array, cfg: snn.SNNConfig
) -> Tuple[Array, Array]:
    """Spike-count argmax prediction + measured events, event-driven path."""
    out_mem, out_spikes, events = event_forward(params, spikes, cfg)
    return snn.predict_from_traces(out_mem, out_spikes), events
