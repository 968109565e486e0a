"""Fused event-driven SNN chunk — one Pallas invocation per Tc-step chunk.

This is the TPU analog of the paper's whole §4.3 pipeline, not just one
stage of it: on the FPGA the event decoder, cascaded adder and LIF neuron
unit are a single circuit and the membrane register never leaves the chip.
The pre-existing kernels each captured half of that — ``aer_spike_matmul``
fused the event gather, ``lif_fused`` fused the membrane update — but the
chunk runtime still stitched them together through HBM: per-step currents
written out by the gather, read back by the LIF pass, and membrane state
round-tripped between every step.  This kernel closes the loop:

  - **per-step event lists ride in through SMEM**: each grid step gets
    one slot's address and value rows of ``ts`` steps as SMEM blocks, and
    the (B*Tc) per-step counts are scalar-prefetched, so event addresses
    can drive dynamic weight-row indexing while scalar memory stays
    bounded by ``ts`` steps of one slot whatever the slot count.  ``ts``
    is the whole chunk where its rows fit SMEM (the 64 px network's
    C=4096) and fewer steps where they do not (C=16384 at 128 px): the
    planner ``analysis.kernel_budget.snn_chunk_staging`` derives it, and
    the kernel's scoped VMEM limit, from the shapes;
  - **membrane potential and refractory counters live in VMEM scratch for
    all Tc steps** — HBM traffic for state is exactly one read of the
    incoming (B, N) slot states and one write of the outgoing ones,
    versus 2*Tc round-trips for the split pipeline;
  - **each E-block's weight-row gathers are gated on a non-silent
    predicate**: event lists are packed valid-first (``runtime.
    step_events``), so a block is silent iff its base offset is past the
    prefetched event count — silent stretches of the capacity cost one
    scalar compare each, and no weight rows are touched (the ROADMAP's
    "gate the weight DMA per E-block" item: on TPU the gather from the
    VMEM-resident slab, and the DMA it implies on spill, simply never
    issues);
  - **hidden layers run as gated in-VMEM matvecs**: the hidden spike plane
    is already resident (it was just computed), so event-extracting it
    would cost more than the (N_hid, N_out) product it feeds — a whole-
    plane non-silent predicate skips even that when the layer is quiet.
    For the paper's 4096-512-2 network >99% of synaptic work is in layer
    0, which takes the gathered path.

Semantics are anchored against ``events.runtime.run_chunk`` (the jnp
oracle): frozen continuous-batching slots, refractory counters, zero and
subtract reset, LIF and Lapicque dynamics, Q1.15 fake-quantized weights,
and measured per-layer event counts all match to float32 tolerance
(tests/test_snn_chunk.py).  On CPU the same kernel runs in interpret mode.

Grid: (B, Tc/ts) — one program per batch slot and block of ``ts`` steps;
weights are broadcast blocks (index map constant over the grid) so each
layer's slab is resident once, and slot programs are embarrassingly
parallel.  A slot's membranes stay in scratch across its step blocks, and
its output blocks stay resident until its last one.  Every per-slot
array carries the slot as a leading axis that its block squeezes
(``None``), so the block's last two dims are whole array dims, as
Mosaic's (8, 128) tiling rule requires at any slot count.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import kernel_budget

Array = jax.Array

_LANE = 128  # TPU lane width: last-dim padding quantum
_EV_PAD = 128  # padded event-count lane (supports up to 128 layers)
# padded neurons get a huge-but-finite threshold: never fires, and unlike
# +inf it cannot make `thr * spike` produce NaN in subtract-reset mode
_PAD_THRESHOLD = 1e30


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _chunk_kernel(
    act_ref,  # (B,) int32 prefetch: 1 = slot active, 0 = frozen
    cnt_ref,  # (B*Tc,) int32 prefetch: valid events per step
    addr_ref,  # (1, ts*C) int32 SMEM block: this slot's event addresses
    val_ref,  # (1, ts*C) f32 SMEM block: signed event values (0 = pad)
    *refs,
    num_layers: int,
    num_steps: int,
    block_steps: int,
    cap: int,
    block_e: int,
    refractory_steps: int,
    reset: str,
    kind: str,
    lapicque_gain: float,
):
    L = num_layers
    ws = refs[0:L]  # (K_i, NP_i) weight slabs
    biases = refs[L : 2 * L]  # (1, NP_i)
    betas = refs[2 * L : 3 * L]
    thrs = refs[3 * L : 4 * L]
    u0s = refs[4 * L : 5 * L]  # (1, NP_i) incoming slot state
    r0s = refs[5 * L : 6 * L]  # (1, NP_i) int32
    mem_ref, spk_ref, ev_ref = refs[6 * L : 6 * L + 3]  # (Tc, NP) per slot
    ufins = refs[6 * L + 3 : 7 * L + 3]
    rfins = refs[7 * L + 3 : 8 * L + 3]
    u_scr = refs[8 * L + 3 : 9 * L + 3]  # VMEM-resident membranes
    r_scr = refs[9 * L + 3 : 10 * L + 3]  # VMEM-resident refractory

    b = pl.program_id(0)
    j = pl.program_id(1)  # block of block_steps steps
    is_active = act_ref[b] > 0
    ne = cap // block_e

    @pl.when(jnp.logical_not(is_active))
    def _frozen():
        # run_chunk semantics for inactive slots: state held, no spikes, no
        # events, output membrane trace pinned at the held value
        for i in range(L):
            ufins[i][...] = u0s[i][...]
            rfins[i][...] = r0s[i][...]
        mem_ref[...] = jnp.broadcast_to(u0s[L - 1][...], mem_ref.shape)
        spk_ref[...] = jnp.zeros_like(spk_ref)
        ev_ref[...] = jnp.zeros_like(ev_ref)

    @pl.when(jnp.logical_and(is_active, j == 0))
    def _load():
        for i in range(L):
            u_scr[i][...] = u0s[i][...]
            r_scr[i][...] = r0s[i][...]

    @pl.when(is_active)
    def _run():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _EV_PAD), 1)

        def step(tb, _):
            t = j * block_steps + tb  # step within the chunk
            # ---- layer 0: gated event-driven synaptic integration
            n0 = cnt_ref[b * num_steps + t]
            base0 = tb * cap

            def eblock(eb, acc):
                base = base0 + eb * block_e

                def gathers(a):
                    # the block's gathers unrolled, in event order: a loop
                    # of one gather a trip spends most of its time on loop
                    # and scalar overhead (3.6x slower at 128 px, 10% at
                    # 64 px on a v5e)
                    for i in range(block_e):
                        addr = addr_ref[0, base + i]
                        v = val_ref[0, base + i]
                        row = ws[0][pl.ds(addr, 1), :].astype(jnp.float32)
                        a = a + row * v
                    return a

                # events are packed valid-first: a block past the count is
                # pure padding — one scalar compare, no row gathers
                return jax.lax.cond(
                    eb * block_e < n0, gathers, lambda a: a, acc
                )

            cur = jax.lax.fori_loop(
                0, ne, eblock, jnp.zeros_like(biases[0][...])
            )
            cur = cur + biases[0][...]

            ev_counts = [n0.astype(jnp.float32)]
            h = None
            for i in range(L):
                if i > 0:
                    # hidden layers: spike plane already VMEM-resident —
                    # gated dense matvec (skip the product when silent),
                    # asked for at full float32 precision rather than at
                    # the MXU's default contract precision
                    hcnt = jnp.sum(h)  # spikes are {0,1}: sum == nnz
                    ev_counts.append(hcnt)
                    w_i, b_i = ws[i], biases[i]
                    cur = jax.lax.cond(
                        hcnt > 0,
                        lambda h=h, w_i=w_i, b_i=b_i: (
                            jnp.dot(
                                h,
                                w_i[...],
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32,
                            )
                            + b_i[...]
                        ),
                        lambda b_i=b_i: b_i[...] + jnp.zeros_like(b_i[...]),
                    )
                # ---- LIF / Lapicque membrane update, state in scratch
                u = u_scr[i][...]
                if kind == "lif":
                    u_pre = betas[i][...] * u + cur
                else:  # lapicque
                    u_pre = u + lapicque_gain * cur
                raw = (u_pre >= thrs[i][...]).astype(jnp.float32)
                if refractory_steps > 0:
                    can = (r_scr[i][...] <= 0).astype(jnp.float32)
                    spk = raw * can
                    r_scr[i][...] = jnp.where(
                        spk > 0,
                        jnp.int32(refractory_steps),
                        jnp.maximum(r_scr[i][...] - 1, 0),
                    )
                else:
                    spk = raw
                if reset == "zero":
                    u_scr[i][...] = u_pre * (1.0 - spk)
                else:  # subtract
                    u_scr[i][...] = u_pre - thrs[i][...] * spk
                h = spk

            mem_ref[pl.ds(t, 1), :] = u_scr[L - 1][...]
            spk_ref[pl.ds(t, 1), :] = h
            ev_row = jnp.zeros((1, _EV_PAD), jnp.float32)
            for i in range(L):
                ev_row = jnp.where(lane == i, ev_counts[i], ev_row)
            ev_ref[pl.ds(t, 1), :] = ev_row
            return 0

        jax.lax.fori_loop(0, block_steps, step, 0)
        # written after every block; the slot's last one is kept
        for i in range(L):
            ufins[i][...] = u_scr[i][...]
            rfins[i][...] = r_scr[i][...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "refractory_steps",
        "reset",
        "kind",
        "lapicque_gain",
        "block_e",
        "interpret",
        "layout",
    ),
)
def snn_chunk(
    weights: Sequence[Array],  # L x (K_i, N_i) f32 (fake-quantized ok)
    biases: Sequence[Array],  # L x (N_i,) f32
    betas: Sequence[Array],  # L x (N_i,) f32 (effective, post-sigmoid)
    thresholds: Sequence[Array],  # L x (N_i,) f32
    u0: Sequence[Array],  # L x (B, N_i) f32 incoming membranes
    r0: Sequence[Array],  # L x (B, N_i) i32 incoming refractory
    addrs: Array,  # (Tc, B, C) int layer-0 event addresses
    values: Array,  # (Tc, B, C) signed event values (0 = pad)
    counts: Array,  # (Tc, B) int valid events per step
    active: Array,  # (B,) slot mask (nonzero = active)
    *,
    refractory_steps: int = 0,
    reset: str = "zero",
    kind: str = "lif",
    lapicque_gain: float = 1.0,
    block_e: int = 128,
    interpret: bool = False,
    layout: str = "time_major",
) -> Tuple[Array, Array, Array, Tuple[Array, ...], Tuple[Array, ...]]:
    """Run the whole SNN ``Tc`` steps in one kernel launch.

    Returns (out_mem (Tc, B, N_last), out_spikes (Tc, B, N_last),
    events (Tc, L, B), u_fin (L x (B, N_i)), refrac_fin (L x (B, N_i))).

    Event lists must be packed valid-first with zero values on padding —
    exactly what ``events.runtime.step_events`` produces; the E-block gate
    relies on it.  Narrow dtypes (int16 addresses, int8 values — the
    device-resident staging format) are widened here, on device, before
    the kernel reads them.  ``layout="slot_major"`` accepts (B, Tc, C) tables —
    the per-slot ring-buffer layout — and skips the transpose the
    time-major layout needs to build the flat per-slot event stream.
    """
    L = len(weights)
    assert L <= _EV_PAD, "event-count lane supports at most 128 layers"
    if layout == "slot_major":
        B, Tc, C = addrs.shape
    elif layout == "time_major":
        Tc, B, C = addrs.shape
    else:
        raise ValueError(f"unknown event layout {layout!r}")

    be = min(block_e, C)
    pc = (-C) % be
    if pc:
        pad = (
            ((0, 0), (0, 0), (0, pc))
        )
        addrs = jnp.pad(addrs, pad)
        values = jnp.pad(values, pad)
    Cp = C + pc

    outs = [w.shape[1] for w in weights]
    np_out = [_round_up(n, _LANE) for n in outs]

    ws, bs, bet, thr, u0p, r0p = [], [], [], [], [], []
    for i in range(L):
        pn = np_out[i] - outs[i]
        w = weights[i].astype(jnp.float32)
        if i > 0:  # rows must match the padded spike plane of layer i-1
            w = jnp.pad(w, ((0, np_out[i - 1] - w.shape[0]), (0, pn)))
        elif pn:
            w = jnp.pad(w, ((0, 0), (0, pn)))
        ws.append(w)
        bs.append(jnp.pad(biases[i].astype(jnp.float32), (0, pn))[None, :])
        bet.append(jnp.pad(betas[i].astype(jnp.float32), (0, pn))[None, :])
        thr.append(
            jnp.pad(
                thresholds[i].astype(jnp.float32),
                (0, pn),
                constant_values=_PAD_THRESHOLD,
            )[None, :]
        )
        u0p.append(
            jnp.pad(u0[i].astype(jnp.float32), ((0, 0), (0, pn)))[:, None]
        )
        r0p.append(
            jnp.pad(r0[i].astype(jnp.int32), ((0, 0), (0, pn)))[:, None]
        )

    # staging derived from the shapes: ts steps of one slot's event rows
    # per grid step, and the scoped VMEM the working set needs
    staging = kernel_budget.snn_chunk_staging(
        [w.shape[0] for w in weights], outs, B, Tc, C, block_e=block_e
    )
    ts = staging.steps_per_block
    nb = Tc // ts

    # flat per-slot event streams, one row per block of ts steps, and
    # per-step counts
    if layout == "slot_major":
        addrs_f = addrs.reshape(B * nb, 1, ts * Cp).astype(jnp.int32)
        values_f = values.reshape(B * nb, 1, ts * Cp).astype(jnp.float32)
        counts_f = counts.reshape(B * Tc).astype(jnp.int32)
    else:
        addrs_f = (
            addrs.transpose(1, 0, 2)
            .reshape(B * nb, 1, ts * Cp)
            .astype(jnp.int32)
        )
        values_f = (
            values.transpose(1, 0, 2)
            .reshape(B * nb, 1, ts * Cp)
            .astype(jnp.float32)
        )
        counts_f = counts.transpose(1, 0).reshape(B * Tc).astype(jnp.int32)
    act = (jnp.asarray(active) != 0).astype(jnp.int32)

    # one slot's (ts*Cp) event rows per grid step: SMEM holds two such
    # blocks (double-buffered) per table, independent of B
    in_specs = [
        pl.BlockSpec(
            (None, 1, ts * Cp),
            lambda b, j, *_: (b * nb + j, 0, 0),
            memory_space=pltpu.SMEM,
        )
        for _ in range(2)
    ]
    for i in range(L):
        # index map constant over the grid: each slab is resident once,
        # shared by every slot program
        in_specs.append(
            pl.BlockSpec(ws[i].shape, lambda b, j, *_: (0, 0))
        )
    for group in (bs, bet, thr):
        for i in range(L):
            in_specs.append(
                pl.BlockSpec((1, np_out[i]), lambda b, j, *_: (0, 0))
            )
    def slot_block(*dims):
        # leading slot axis squeezed: the kernel sees the trailing dims;
        # constant over a slot's step blocks, so outputs stay resident
        # until its last one
        return pl.BlockSpec(
            (None, *dims), lambda b, j, *_: (b,) + (0,) * len(dims)
        )

    for group in (u0p, r0p):
        for i in range(L):
            in_specs.append(slot_block(1, np_out[i]))

    npl = np_out[-1]
    out_specs = [
        slot_block(Tc, npl),  # mem
        slot_block(Tc, npl),  # spikes
        slot_block(Tc, _EV_PAD),  # events
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, Tc, npl), jnp.float32),
        jax.ShapeDtypeStruct((B, Tc, npl), jnp.float32),
        jax.ShapeDtypeStruct((B, Tc, _EV_PAD), jnp.float32),
    ]
    for i in range(L):  # final membranes
        out_specs.append(slot_block(1, np_out[i]))
        out_shape.append(jax.ShapeDtypeStruct((B, 1, np_out[i]), jnp.float32))
    for i in range(L):  # final refractory counters
        out_specs.append(slot_block(1, np_out[i]))
        out_shape.append(jax.ShapeDtypeStruct((B, 1, np_out[i]), jnp.int32))

    scratch_shapes = [pltpu.VMEM((1, np_out[i]), jnp.float32) for i in range(L)]
    scratch_shapes += [pltpu.VMEM((1, np_out[i]), jnp.int32) for i in range(L)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    results = pl.pallas_call(
        functools.partial(
            _chunk_kernel,
            num_layers=L,
            num_steps=Tc,
            block_steps=ts,
            cap=Cp,
            block_e=be,
            refractory_steps=refractory_steps,
            reset=reset,
            kind=kind,
            lapicque_gain=lapicque_gain,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=staging.vmem_limit_bytes,
        ),
        interpret=interpret,
    )(act, counts_f, addrs_f, values_f, *ws, *bs, *bet, *thr, *u0p, *r0p)

    mem, spk, ev = results[0], results[1], results[2]
    u_fin = tuple(results[3 + i][:, 0, : outs[i]] for i in range(L))
    r_fin = tuple(results[3 + L + i][:, 0, : outs[i]] for i in range(L))
    n_last = outs[-1]
    events = ev[:, :, :L].transpose(1, 2, 0)  # (Tc, L, B)
    return (
        mem[:, :, :n_last].transpose(1, 0, 2),
        spk[:, :, :n_last].transpose(1, 0, 2),
        events,
        u_fin,
        r_fin,
    )
