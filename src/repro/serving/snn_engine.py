"""Streaming SNN serving engine: device-resident spike trains, async
admission, deadline-aware scheduling, pipelined ticks.

The LM ``ServeEngine`` batches token sequences; spiking workloads stream
*time*: each request is a spike train (rate-coded image or DVS event
stream) that must be integrated over its coding window while the neuron
membranes persist between chunks.  The paper's case study — collision
avoidance — is a latency-critical, always-on workload, so the engine is an
*async* scheduler rather than a one-shot batch loop:

- **submit()/poll()/drain().** Requests arrive at any time, including
  while chunks are in flight.  ``submit`` enqueues (returning a request
  id); ``poll`` admits queued requests into free slots and advances every
  active slot by one chunk, returning whatever finished; ``drain`` polls
  until the engine is idle.  ``run(requests)`` survives as a thin
  batch-compatibility wrapper.
- **EDF admission.** Each request carries an optional relative
  ``deadline_s`` and an integer ``priority``.  The queue is ordered by
  (priority desc, earliest absolute deadline first, FIFO); every result
  reports its queue wait and whether its deadline was missed, and the
  engine tracks an episode-level miss rate.
- **Device-resident, event-compressed spike trains.** Admission uploads a
  request's input exactly once: images are rate-encoded *on device* (no
  host-side encode + re-upload), dense trains are event-compressed on
  device into a packed per-step AER table (int16 addresses, int8 signed
  values — ``events.aer.StepEventTable``) and staged into a per-slot ring
  buffer that lives in device memory for the request's whole lifetime.
  The jitted chunk function ``dynamic_slice``s each slot's next ``Tc``
  steps by its on-device ``done`` offset and feeds them straight to
  ``runtime.run_chunk_events`` — no per-chunk host assembly, no per-chunk
  H2D transfer, no re-extraction of layer-0 events.  At the collision
  config's autotuned capacity the staged table is a measured ~4.7x
  smaller than the dense float32 planes the pre-residency engine shipped
  every chunk (``BENCH_snn.json`` host_overhead.resident_chunk_bytes).
- **Pipelined ticks.** The chunk's per-slot scheduling metadata (``done``
  offsets, window lengths, admit flags) lives on device and is advanced
  *inside* the chunk, so a steady-state tick passes no host arrays at
  all; state and metadata buffers are donated.  Completion stats land in
  a one-deep future queue: chunk N+1 dispatches before chunk N's stats
  are fetched, overlapping host bookkeeping and the single D2H stats
  fetch with device compute (``pipeline_depth=0`` restores the
  synchronous tick for debugging).  Ticks whose dispatch completes a
  request's window retire eagerly, so completion — and the deadline
  verdict — never waits an extra poll round.  A steady mid-window tick
  performs exactly one host transfer — the stats fetch — which
  ``tests/test_snn_resident.py`` pins down under ``jax.transfer_guard``.
- **Slots.** A fixed micro-batch of ``num_slots`` concurrent requests
  shares one compiled event-driven chunk step.  Per-slot membrane +
  refractory state lives across chunks; slot shapes are static so nothing
  recompiles.  Slot turnover (zeroing state on admit) happens *inside*
  the jitted chunk function via a device-side admit flag.
- **Sharded slots.** Pass ``mesh=`` to shard the slot axis — states,
  rings, metadata and stats alike — over the mesh
  (``distributed.partitioning`` slot/ring rules + ``shard_map``), scaling
  ``num_slots`` past one device while keeping the single-compiled-chunk
  invariant and jnp/fused backend parity.
- **Measured energy.** Every chunk reports per-step, per-layer event
  counts.  A request's energy estimate is priced from the events it
  *actually* generated via ``core.energy.snn_ops_from_events`` — not from
  an assumed spike rate.
- **Observability.** The engine carries a ``repro.obs`` metrics registry
  (``engine.metrics``) and span recorder (``engine.trace``) instead of
  ad-hoc scalar accumulators: per-request latency / queue-wait / energy
  histograms, episode-scoped counters (events, steps, completions,
  deadline misses — reset when an episode opens, so nothing goes stale
  across episodes) and per-tick phase histograms.  Every place the
  serving thread spends time is a phase (``engine.poll``,
  ``engine.submit``, ``engine.admit`` with ``.upload`` and ``.dispatch``,
  ``engine.tick.prep``, ``engine.tick.dispatch``, ``engine.retire`` with
  ``.fetch``, ``engine.finalize``, ``engine.sample``): a
  ``jax.profiler.TraceAnnotation``, so a profiler capture shows each
  against the device's operations, and, with ``trace_capacity > 0``, a
  span in the ring beside the request lifecycle (submit -> queue ->
  engine.admit -> occupy -> complete).  ``metrics_snapshot()`` exports
  JSON-able instrument state; ``export_trace(path)`` writes a
  Perfetto-loadable Chrome trace.  The recording cost is host-side only
  (the jitted chunk is untouched) and ``benchmarks/stream_bench.py``
  pins it under 2% of a tick.
- **Time series + SLOs.** A ``TimeSeriesSampler`` (``engine.timeseries``)
  captures a registry delta on every tick and admission, turning the
  lifetime counters into *windowed* rates — events/s, ticks/s,
  ``windowed_miss_rate()`` — and ``health()`` judges the engine's SLO
  specs (deadline-miss error budget, p99 latency target; override via
  the ``slos=`` init arg) with multi-window burn-rate rules over that
  series, publishing ``healthy``/``degraded``/``breach`` as the
  ``engine.slo.status`` gauge.  These windowed signals are what the
  fleet/admission-plane work (ROADMAP item 1) sheds load against.
- **Fault tolerance** (``repro.faults``).  Pass ``admission=`` an
  ``AdmissionPolicy`` to enable load shedding: a bounded admission
  queue sheds (or parks, for ``priority > 0``) at ``submit()`` once
  full, and an EDF feasibility check at admission-pop time sheds
  requests whose deadline is provably unmeetable from the measured
  trailing-window tick rate — both surface as ``StreamResult``s with
  ``disposition="shed"`` instead of guaranteed misses.  With
  ``fault_checks=True`` (default) the chunk carries in-graph NaN/inf
  membrane checks, staged-ring count/address range checks, and a
  staging capacity-overflow check; a poisoned request is *quarantined*
  (``disposition="quarantined"`` + fault code, slot freed, state
  sanitized in-graph) while the other S-1 slots keep ticking
  bit-identically.  Chunk dispatch runs under a retry supervisor
  (capped exponential backoff) that permanently demotes
  ``backend="fused"`` to ``"jnp"`` after persistent failures — one
  ``RuntimeWarning``, counted in ``engine.faults.backend_demoted``.
  ``drain(timeout_s=...)`` raises ``EngineStallError`` with a
  per-slot diagnostic snapshot instead of looping forever on a wedged
  engine, and ``health()`` gains a ``diagnosis`` block separating
  "overloaded and shedding correctly" from "faulty".  A seeded
  ``faults.FaultInjector`` (``injector=``) drives the chaos suite in
  ``tests/test_faults.py`` and the bench's ``fault_tolerance`` block.
- **Crash-safe state.** ``snapshot(path)`` serializes the engine's
  *complete* serving state — per-slot membrane/refractory rows, packed
  AER rings, on-device scheduling metadata, host bookkeeping, the
  admission queue, parked requests, the preemption parking buffer, and
  undelivered results — through the checkpoint plane's atomic
  tmp-dir+rename+checksum discipline.  ``restore(path)`` on a freshly
  built engine (same params/config) resumes every in-flight window
  **bit-exactly**: float32 membranes and int8/int16 event tables round-
  trip through npz unchanged, so a warm-restarted engine's results are
  bit-identical to an uninterrupted run (``tests/test_recovery.py``).
  ``snapshot_auto``/``restore_latest_snapshot`` add a keep-N rotation
  with corrupt-snapshot fallback (checksum failure -> loud warning +
  ``engine.faults.checkpoint_fallback`` counter, previous snapshot
  restored).  Absolute wall-clock state (deadlines, submit times) is
  persisted as remaining-budget/ages and re-anchored at restore —
  ``perf_counter`` values are meaningless across processes.
- **Deadline-aware preemption** (``preempt=True``).  When a strictly
  tighter-urgency request arrives with every slot busy, the loosest
  resident window is *parked* — state rows, staged ring row, and
  accumulators move to a host-side parking buffer — the urgent window
  runs, and the parked window resumes from the exact step it stopped
  at (admit flag stays 0, so the chunk does not zero the restored
  membranes; mid-window park/restore is bit-exact).  Parking costs one
  D2H + one H2D of a single slot's rows, measured per event in
  ``engine.preempt.park_s`` / ``restore_s`` histograms and the
  ``engine.preempt.parked_events`` counter; ``health()`` flags
  ``preempt_thrash`` when the park rate outruns completions.
"""

from __future__ import annotations

import collections
import dataclasses
import heapq
import os
import shutil
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import kernel_budget
from repro.checkpoint.manager import (
    CheckpointCorruptError,
    gc_orphan_tmpdirs,
    load_array_dir,
    publish_array_dir,
)
from repro.core import coding, energy, neuron, snn
from repro.distributed import partitioning
from repro.events import aer, runtime
from repro.events import capacity as cap_mod
from repro.faults import shedding as shed_mod
from repro.faults.supervisor import ChunkSupervisor, RetryPolicy
from repro.obs import MetricsRegistry, TimeSeriesSampler, TraceRecorder
from repro.obs import slo as slo_mod

Array = jax.Array

# chunk fault bitmask (device-side detection -> host quarantine codes)
FAULT_NONFINITE_STATE = 1
FAULT_RING_CORRUPT = 2
FAULT_CAPACITY_OVERFLOW = 4
_FAULT_NAMES = {
    FAULT_NONFINITE_STATE: "nonfinite_state",
    FAULT_RING_CORRUPT: "ring_corrupt",
    FAULT_CAPACITY_OVERFLOW: "capacity_overflow",
}


def _slot_row_writer(mesh, num_slots: int):
    """``write(x, slot, value)``: ``x`` with ``value`` written at the
    front of row ``slot`` of its leading (slot) axis, i.e.
    ``x[slot, :value.shape[0], ...] = value``.

    Without a mesh this is one ``dynamic_update_slice``.  On a mesh each
    shard runs the same update on its local slot block and keeps its own
    row where ``slot`` lies in another shard's block, so the write touches
    one row on every layout and needs no sharding annotation on an
    Explicit mesh."""

    def local(x, slot, value):
        start = (slot,) + (0,) * (x.ndim - 1)
        return jax.lax.dynamic_update_slice(
            x, jnp.asarray(value, x.dtype)[None], start
        )

    if mesh is None:
        return local
    axis = partitioning.slot_axis(num_slots, mesh)

    def shard(x, slot, value):
        n = x.shape[0]  # slots in this shard's block
        i = slot - jax.lax.axis_index(axis) * n
        mine = (i >= 0) & (i < n)
        start = (jnp.clip(i, 0, n - 1),) + (0,) * (x.ndim - 1)
        row = jnp.asarray(value, x.dtype)[None]
        old = jax.lax.dynamic_slice(x, start, row.shape)
        return jax.lax.dynamic_update_slice(
            x, jnp.where(mine, row, old), start
        )

    return jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=P(axis),
        check_vma=False,
    )


def _host_row(x: Array, s: int) -> np.ndarray:
    """Row ``s`` of ``x``'s leading (slot) axis, on the host, read from
    the one shard that holds it: no gather, so a slot-sharded ``x`` on an
    Explicit mesh needs no sharding annotation."""
    for shard in x.addressable_shards:
        lo, hi, _ = shard.index[0].indices(x.shape[0])
        if lo <= s < hi:
            return np.asarray(jax.device_get(shard.data[s - lo]))
    raise IndexError(f"row {s} of {x.shape} is on no addressable shard")


def fault_code_names(code: int) -> str:
    """Human-readable ``+``-joined names of a chunk fault bitmask."""
    names = [n for bit, n in sorted(_FAULT_NAMES.items()) if code & bit]
    return "+".join(names) if names else f"unknown({code})"


class EngineStallError(RuntimeError):
    """``drain(timeout_s=...)`` expired with the engine not idle.

    ``snapshot`` is the per-slot diagnostic state at expiry
    (``SNNStreamEngine.stall_snapshot()``); ``results`` holds whatever
    completed before the stall.
    """

    def __init__(self, message: str, snapshot: Dict, results):
        super().__init__(message)
        self.snapshot = snapshot
        self.results = list(results)


@dataclasses.dataclass
class StreamRequest:
    """One inference over a spike stream.

    Provide either ``image`` ((K,) floats in [0,1], rate-encoded on the
    device at admission) or ``spikes`` ((T, K) pre-encoded train, e.g.
    densified DVS events; values must be integer-valued spike magnitudes
    in [-127, 127] — {0,1} rate/TTFS codes and {-1,0,1} DVS polarities
    all are — because trains are staged device-side as packed int8/int16
    AER event tables).

    ``deadline_s`` is relative to submission time; a request that finishes
    later is still served but reported (and counted) as missed.  Higher
    ``priority`` admits sooner; within a priority class admission is
    earliest-deadline-first, then FIFO (deadline-less requests last).
    """

    image: Optional[np.ndarray] = None
    spikes: Optional[np.ndarray] = None
    num_steps: Optional[int] = None  # None -> cfg.num_steps (must be >= 1)
    deadline_s: Optional[float] = None  # relative latency budget
    priority: int = 0


@dataclasses.dataclass
class StreamResult:
    request_id: int
    prediction: int
    spike_counts: np.ndarray  # (n_class,) output spike counts
    steps: int
    latency_s: float  # submit -> finish (includes queue wait)
    queue_wait_s: float  # submit -> admission into a slot
    events_per_layer: np.ndarray  # (n_layers,) measured input events
    spike_rate: float  # measured mean input rate of layer 0
    energy_pj: float  # priced from measured events
    deadline_s: Optional[float] = None  # the request's relative budget
    deadline_missed: bool = False
    # fault-tolerance dispositions: "ok" (served), "shed" (rejected by
    # the admission plane — never entered a slot), "quarantined"
    # (poisoned mid-flight; slot reset, stats discarded).  ``fault``
    # carries the shed reason or quarantine fault-code names; ``parked``
    # marks a priority request that was parked under overload and later
    # served best-effort.
    disposition: str = "ok"
    fault: Optional[str] = None
    parked: bool = False


def _doc_result(r: StreamResult) -> Dict:
    """JSON-able form of a StreamResult (snapshot manifest); the small
    per-class arrays ride in the manifest as lists."""
    return {
        "request_id": r.request_id,
        "prediction": r.prediction,
        "spike_counts": [float(x) for x in np.ravel(r.spike_counts)],
        "steps": r.steps,
        "latency_s": r.latency_s,
        "queue_wait_s": r.queue_wait_s,
        "events_per_layer": [
            float(x) for x in np.ravel(r.events_per_layer)
        ],
        "spike_rate": r.spike_rate,
        "energy_pj": r.energy_pj,
        "deadline_s": r.deadline_s,
        "deadline_missed": bool(r.deadline_missed),
        "disposition": r.disposition,
        "fault": r.fault,
        "parked": bool(r.parked),
    }


def _undoc_result(d: Dict) -> StreamResult:
    return StreamResult(
        request_id=d["request_id"],
        prediction=d["prediction"],
        spike_counts=np.asarray(d["spike_counts"], np.float64),
        steps=d["steps"],
        latency_s=d["latency_s"],
        queue_wait_s=d["queue_wait_s"],
        events_per_layer=np.asarray(d["events_per_layer"], np.float64),
        spike_rate=d["spike_rate"],
        energy_pj=d["energy_pj"],
        deadline_s=d["deadline_s"],
        deadline_missed=d["deadline_missed"],
        disposition=d["disposition"],
        fault=d["fault"],
        parked=d["parked"],
    )


class SNNStreamEngine:
    """Async-admission, deadline-aware scheduler over device-resident
    event rings and the event-driven SNN chunk runtime."""

    def __init__(
        self,
        params: Dict[str, Dict[str, Array]],
        cfg: snn.SNNConfig,
        *,
        num_slots: int = 8,
        chunk_steps: int = 5,
        seed: int = 0,
        backend: str = "auto",
        capacities: Optional[Sequence[int]] = None,
        mesh=None,
        pipeline_depth: int = 1,
        trace_capacity: int = 0,
        timeseries_capacity: int = 4096,
        slos: Optional[Sequence] = None,
        admission: Optional[shed_mod.AdmissionPolicy] = None,
        fault_checks: bool = True,
        injector=None,
        retry: Optional[RetryPolicy] = None,
        preempt: bool = False,
    ):
        self.params = params
        self.cfg = cfg
        self.S = num_slots
        self.Tc = chunk_steps
        self._rng = jax.random.PRNGKey(seed)
        self.slos = (
            tuple(slos) if slos is not None else slo_mod.default_slos()
        )
        self._make_instruments(trace_capacity, timeseries_capacity)
        # prepare (fake-quantize) once at init — the original loop re-ran
        # the full weight-set quantization inside every chunk execution
        self._prepared = jax.device_put(
            runtime.prepare_params(params, cfg),
            None if mesh is None else NamedSharding(mesh, P()),
        )
        # "auto" resolves here, once, so health() and snapshots name the
        # kernel that actually runs
        self.backend = runtime.resolve_backend(backend)
        self.mesh = mesh
        # every slot-indexed array (states, rings, meta) lives split over
        # the mesh's slot axes; None keeps it on the default device
        self._slot_axis = (
            None if mesh is None else partitioning.slot_axis(num_slots, mesh)
        )
        # one-row writes into slot-indexed arrays (admission, park and
        # resume); _write_row holds the eager, buffer-donating form for
        # each array rank
        self._row_writer = _slot_row_writer(mesh, num_slots)
        self._write_row = {
            nd: self._slot_jit(self._row_writer, nd, donate_argnums=0)
            for nd in (1, 2, 3)
        }
        self.pipeline_depth = max(0, int(pipeline_depth))
        self.capacities = (
            tuple(int(c) for c in capacities)
            if capacities is not None
            else None
        )
        # fault-tolerance plane: admission policy (None = historical
        # admit-everything behavior), in-graph fault checks, retry/
        # demotion supervisor, optional deterministic fault injector
        self.admission = admission
        self.fault_checks = bool(fault_checks)
        self.injector = injector
        # deadline-aware slot preemption (opt-in): a strictly tighter-
        # urgency arrival may park the loosest resident window
        self.preempt = bool(preempt)
        self._snap_index = 0  # snapshot_auto rotation counter
        self._supervisor = ChunkSupervisor(
            retry or RetryPolicy(),
            on_retry=lambda n: self._m_retries.inc(n),
            on_demote=lambda: self._m_demoted.inc(),
        )
        # staged event-table geometry: layer-0 capacity bounds every
        # per-step event list; int16 addresses whenever fan-in fits
        self.C = cap_mod.input_capacity(cfg, self.capacities)
        self._addr_dtype = aer.addr_dtype_for(cfg.layer_sizes[0])
        self._ring_steps = max(int(cfg.num_steps), chunk_steps)

        self._chunk, self._chunk_nodonate = self._build_chunk(self.backend)
        # compile-site allowlist: one cold-start compile of the fresh
        # chunk; _grow_ring and demotion bump/reset it (known sites)
        self._chunk_compiles_expected = 1
        self._chunk_compiles_accounted = 0
        self._make_admit_fns()
        self._reset_all()

    def _build_chunk(self, backend: str):
        """Build (and jit) the tick chunk for ``backend``; returns the
        (donating, non-donating) pair.  Called at init and again by the
        supervisor's demotion path to rebuild the chunk on ``jnp`` after
        persistent fused failures."""
        cfg = self.cfg
        Tc, C = self.Tc, self.C
        K0 = cfg.layer_sizes[0]
        fault_checks = self.fault_checks
        capacities = self.capacities
        mesh, num_slots = self.mesh, self.S
        self._plan_kernel(backend)

        def _chunk_fn(prepared, states, ring, meta):
            # scheduling metadata lives on device: per-slot consumed-step
            # offsets, window lengths, and admit flags.  take/active are
            # derived here, and ``done`` advances in-graph, so a
            # steady-state tick uploads nothing.
            done, total, admit = meta["done"], meta["total"], meta["admit"]
            fault_in = meta["fault"]
            take = jnp.clip(total - done, 0, Tc)
            act = (take > 0).astype(jnp.float32)
            # in-jit slot turnover: slots admitted since the previous
            # chunk start from zeroed membrane/refractory state here,
            # inside the compiled function
            fresh = admit[:, None] > 0
            states = [
                neuron.NeuronState(
                    u=jnp.where(fresh, 0.0, st.u),
                    refrac=jnp.where(fresh, 0, st.refrac),
                )
                for st in states
            ]
            # each slot's next Tc steps, sliced from its resident ring
            # (slot-major (S, Tc, C) — consumed transpose-free)
            a_c = jax.vmap(
                lambda r, d: jax.lax.dynamic_slice(r, (d, 0), (Tc, C))
            )(ring["addrs"], done)
            v_c = jax.vmap(
                lambda r, d: jax.lax.dynamic_slice(r, (d, 0), (Tc, C))
            )(ring["values"], done)
            c_c = jax.vmap(
                lambda r, d: jax.lax.dynamic_slice(r, (d,), (Tc,))
            )(ring["counts"], done)
            # silence steps past the request's window: the ring beyond a
            # request's T steps holds a previous occupant's stale events,
            # and the final ragged chunk of a window slices into it
            # (shapes broadcast from ``take`` so the same body runs on a
            # shard_map-local slot block)
            in_window = (
                jnp.arange(Tc, dtype=jnp.int32)[None, :] < take[:, None]
            )
            values = jnp.where(
                in_window[:, :, None], v_c.astype(jnp.float32), 0.0
            )
            counts = jnp.where(in_window, c_c, 0)
            new_states, out_mem, out_spikes, events = (
                runtime.run_chunk_events(
                    prepared,
                    states,
                    a_c.astype(jnp.int32),
                    values,
                    counts,
                    cfg,
                    active=act,
                    capacities=capacities,
                    prepared=True,
                    backend=backend,
                    layout="slot_major",
                )
            )
            # in-graph fault detection: per-slot bitmask riding the same
            # stats pytree (so quarantine costs zero extra transfers).
            # Detection is masked to the request's own window — stale
            # ring contents past ``take`` can't false-positive — and
            # faulted slots' state is sanitized to zero in-graph
            # (jnp.where is a bit-exact no-op for clean slots), so a
            # poisoned slot self-heals while its host-side quarantine
            # is in flight and never contaminates a later occupant.
            fault = fault_in
            if fault_checks:
                bad_state = jnp.zeros(done.shape, bool)
                for st in new_states:
                    bad_state = bad_state | jnp.any(
                        ~jnp.isfinite(st.u), axis=-1
                    )
                bad_count = jnp.any(
                    (counts < 0) | (counts > C), axis=-1
                )
                ev_valid = in_window[:, :, None] & (
                    jnp.arange(C, dtype=jnp.int32)[None, None, :]
                    < jnp.clip(counts, 0, C)[:, :, None]
                )
                a32 = a_c.astype(jnp.int32)
                bad_addr = jnp.any(
                    ev_valid & ((a32 < 0) | (a32 >= K0)), axis=(1, 2)
                )
                fault = (
                    fault
                    | jnp.where(bad_state, 1, 0).astype(jnp.int32)
                    | jnp.where(bad_count | bad_addr, 2, 0).astype(
                        jnp.int32
                    )
                )
                poisoned = (fault > 0)[:, None]
                new_states = [
                    neuron.NeuronState(
                        u=jnp.where(poisoned, 0.0, st.u),
                        refrac=jnp.where(poisoned, 0, st.refrac),
                    )
                    for st in new_states
                ]
            # per-slot stats accumulate on device; only the request's own
            # steps (take per slot) count toward its result
            m = (
                jnp.arange(Tc, dtype=jnp.int32)[:, None] < take[None, :]
            ).astype(jnp.float32)
            stats = {
                "counts": jnp.sum(out_spikes * m[:, :, None], axis=0),
                "memsum": jnp.sum(out_mem * m[:, :, None], axis=0),
                "events": jnp.sum(events * m[:, None, :], axis=0).T,
                "fault": fault,
            }
            new_meta = {
                "done": done + take,
                "total": total,
                "admit": jnp.zeros_like(admit),
                # fault codes report exactly once: staged overflow bits
                # surface in this chunk's stats, then clear
                "fault": jnp.zeros_like(fault_in),
            }
            return new_states, new_meta, stats

        if mesh is None:
            body = _chunk_fn
        else:
            body = self._shard_over_slots(_chunk_fn, mesh, num_slots)
        # output ranks: states (S, N) rows, (S,) meta; stats go to the
        # host and are never fed back, so their prefix needs no full rank
        ranks = (
            [neuron.NeuronState(u=2, refrac=2)] * cfg.num_layers, 1, 1
        )
        # states + metadata are donated: the tick loop threads them
        # through the compiled chunk without ever copying them back out
        return (
            self._slot_jit(body, ranks, donate_argnums=(1, 3)),
            self._slot_jit(body, ranks),
        )

    def _plan_kernel(self, backend: str) -> None:
        """Set the gauges of what one fused kernel call stages, as the
        planner prices it from the shapes (slots per device on a mesh);
        zero on a backend that runs no fused kernel."""
        plan = None
        if backend == "fused":
            sizes = self.cfg.layer_sizes
            slots = (self.S if self.mesh is None else
                     self._slot_sharding(1).shard_shape((self.S,))[0])
            plan = kernel_budget.snn_chunk_staging(
                sizes[:-1], sizes[1:], slots, self.Tc, self.C)
        self._m_k_weight.set(plan.weight_bytes if plan else 0)
        self._m_k_events.set(plan.event_row_bytes if plan else 0)
        self._m_k_vmem.set(plan.vmem_limit_bytes if plan else 0)

    def _slot_sharding(self, ndim: int) -> NamedSharding:
        """The one sharding of a rank-``ndim`` slot-leading array on the
        mesh: split on the slot axis, full rank.  Jitted outputs on a
        mesh may carry an equivalent spec of another rank (``P(slot)``
        for ``P(slot, None)``), which the jit cache treats as a new
        input sharding, so every producer of a slot array states this
        one."""
        return NamedSharding(
            self.mesh, P(self._slot_axis, *(None,) * (ndim - 1))
        )

    def _slot_jit(self, fn, ranks, **kw):
        """``jax.jit(fn)`` whose outputs, on a mesh, carry
        ``_slot_sharding`` of the ranks in ``ranks`` (a pytree prefix of
        the outputs)."""
        if self.mesh is None:
            return jax.jit(fn, **kw)
        return jax.jit(
            fn, out_shardings=jax.tree.map(self._slot_sharding, ranks), **kw
        )

    @staticmethod
    def _shard_over_slots(chunk_fn, mesh, num_slots: int):
        """Wrap the chunk function in shard_map with the slot axis split
        over the mesh's batch axes (``distributed.partitioning`` slot and
        ring rules).

        Params are replicated; states, event rings, scheduling metadata
        and stats all shard along slots (a ``P(slot)`` pytree prefix —
        rings keep their ring_steps/event_cap dims local to the slot's
        shard).  The chunk body is elementwise over slots, so sharding is
        exact — jnp/fused parity and the single-compiled-chunk invariant
        carry over unchanged.
        """
        slot = partitioning.slot_axis(num_slots, mesh)
        return jax.shard_map(
            chunk_fn,
            mesh=mesh,
            # (params, states, ring, meta) — P(slot) prefixes shard the
            # leading slot axis of every states/ring/meta leaf
            in_specs=(P(), P(slot), P(slot), P(slot)),
            out_specs=(P(slot), P(slot), P(slot)),
            check_vma=False,
        )

    # ------------------------------------------------- device admission
    def _make_admit_fns(self):
        """Jitted staging: encode + compress a request's train on device
        and write it into the slot's ring, updating device metadata.

        Ring and metadata buffers are donated — each admission rewrites
        the slot's first T ring steps and its metadata in place
        (device-side, on every mesh shard alike), costing one small H2D
        upload (the train or the raw image) and zero host round-trips.
        """
        C = self.C
        adt = self._addr_dtype
        fault_checks = self.fault_checks
        write = self._row_writer

        def stage(ring, meta, train, slot):
            T = train.shape[0]
            table = runtime.encode_step_table(train, C, addr_dtype=adt)
            ring = {
                "addrs": write(ring["addrs"], slot, table.addrs),
                "values": write(ring["values"], slot, table.values),
                "counts": write(ring["counts"], slot, table.counts),
            }
            if fault_checks:
                # capacity overflow: a step with more nonzero inputs
                # than the layer-0 capacity C would be *silently
                # truncated* by the packed table — flag the slot so the
                # first chunk quarantines it instead of serving a
                # wrong-by-construction result
                nnz = jnp.sum(train != 0.0, axis=-1)
                fcode = jnp.where(
                    jnp.any(nnz > C), FAULT_CAPACITY_OVERFLOW, 0
                ).astype(jnp.int32)
            else:
                fcode = jnp.int32(0)
            meta = {
                "done": write(meta["done"], slot, 0),
                "total": write(meta["total"], slot, T),
                "admit": write(meta["admit"], slot, 1),
                "fault": write(meta["fault"], slot, fcode),
            }
            return ring, meta

        def admit_spikes(ring, meta, train, slot):
            return stage(ring, meta, train, slot)

        def admit_image(ring, meta, image, key, slot, T):
            # rate-encode on device: the image is the only upload; the
            # dense (T, K) train never exists host-side at all
            train = coding.rate_encode(key, image, T)
            return stage(ring, meta, train, slot)

        # output ranks: the (S, R, C) / (S, R) ring and (S,) meta
        ranks = ({"addrs": 3, "values": 3, "counts": 2}, 1)
        self._admit_spikes_fn = self._slot_jit(
            admit_spikes, ranks, donate_argnums=(0, 1)
        )
        self._admit_image_fn = self._slot_jit(
            admit_image, ranks, donate_argnums=(0, 1), static_argnames=("T",)
        )

    def _alloc_ring(self, ring_steps: int) -> Dict[str, Array]:
        # Tc steps of zero padding keep the chunk's dynamic_slice
        # in-bounds (never offset-clamped) at every done offset in
        # [0, ring_steps]
        S, Tc, C = self.S, self.Tc, self.C
        R = ring_steps + Tc
        return self._on_slots({
            "addrs": jnp.zeros((S, R, C), self._addr_dtype),
            "values": jnp.zeros((S, R, C), jnp.int8),
            "counts": jnp.zeros((S, R), jnp.int32),
        })

    def _put_row(self, x: Array, s: int, value) -> Array:
        """Eager one-row write (``_slot_row_writer``) into slot-indexed
        ``x``, whose buffer is donated."""
        return self._write_row[x.ndim](x, np.int32(s), value)

    def _on_slots(self, tree):
        """Place a pytree of slot-leading arrays: split over the mesh's
        slot axes when the engine has a mesh, else on the default
        device.  On a mesh each array gets ``_slot_sharding``, the
        sharding every jitted producer of slot arrays gives its outputs,
        so the chunk sees one input sharding and compiles once."""
        if self.mesh is None:
            return jax.device_put(tree)
        return jax.tree.map(
            lambda x: jax.device_put(x, self._slot_sharding(x.ndim)), tree
        )

    def _grow_ring(self, T: int) -> None:
        """Grow the rings to hold a T-step train (T > current capacity).

        One-time reallocation + device-side copy; other slots' staged
        trains survive.  The chunk function recompiles once for the new
        ring shape (shapes are static thereafter).
        """
        grow = int(T) - self._ring_steps
        self._ring_steps = int(T)
        self._ring = self._on_slots({
            k: jnp.pad(v, ((0, 0), (0, grow)) + ((0, 0),) * (v.ndim - 2))
            for k, v in self._ring.items()
        })
        # a larger ring is a new chunk input shape: one more compile is
        # a known site, not a steady-state recompile
        self._chunk_compiles_expected += 1

    # ----------------------------------------------------- observability
    def _make_instruments(
        self, trace_capacity: int, timeseries_capacity: int
    ) -> None:
        """Create the engine's metrics registry, span recorder (its ring
        off at ``trace_capacity=0``), and windowed time-series sampler.

        Episode-scoped counters live under ``engine.episode.`` and reset
        when an episode opens (first submit on an idle engine); request
        histograms and tick-phase histograms are engine-lifetime (reset
        them explicitly via ``metrics.reset(prefix=...)`` or
        ``reset_tick_stats``).  The sampler captures a registry delta
        on every tick and every admission (bounded ring; restart it via
        ``timeseries.restart()`` after warmup) — the signal ``health()``
        evaluates the engine's SLOs against.
        """
        self.metrics = MetricsRegistry()
        self.trace = TraceRecorder(capacity=trace_capacity)
        m = self.metrics
        # episode-scoped (reset at _begin_episode)
        self._m_events = m.counter("engine.episode.events")
        self._m_steps = m.counter("engine.episode.steps")
        self._m_completed = m.counter("engine.episode.completed")
        self._m_misses = m.counter("engine.episode.deadline_misses")
        self._m_wall = m.gauge("engine.episode.wall_s")
        # engine-lifetime request instruments
        self._m_submitted = m.counter("engine.requests.submitted")
        self._m_finished = m.counter("engine.requests.completed")
        self._m_missed_total = m.counter("engine.requests.deadline_missed")
        self._m_latency = m.histogram(
            "engine.request.latency_s", lo=1e-6, hi=1e3
        )
        self._m_qwait = m.histogram(
            "engine.request.queue_wait_s", lo=1e-6, hi=1e3
        )
        self._m_energy = m.histogram(
            "engine.request.energy_pj", lo=1.0, hi=1e12
        )
        # tick-phase timing (reset via reset_tick_stats)
        self._m_prep = m.histogram(
            "engine.tick.host_prep_s", lo=1e-7, hi=10.0
        )
        self._m_dispatch = m.histogram(
            "engine.tick.dispatch_s", lo=1e-7, hi=10.0
        )
        self._m_fetch = m.histogram(
            "engine.tick.stats_fetch_s", lo=1e-7, hi=10.0
        )
        self._m_qdepth = m.gauge("engine.queue.depth")
        self._m_active = m.gauge("engine.slots.active")
        # the fused kernel's plan per call (_plan_kernel): layer-0 weight
        # bytes brought into VMEM, event-row bytes staged into SMEM, and
        # the scoped VMEM limit it is given
        self._m_k_weight = m.gauge("engine.kernel.layer0_weight_bytes")
        self._m_k_events = m.gauge("engine.kernel.event_row_bytes")
        self._m_k_vmem = m.gauge("engine.kernel.vmem_limit_bytes")
        # fault-tolerance instruments: admission-plane dispositions
        # (lifetime), chunk-supervisor events, injector applications,
        # and the episode-scoped exclusion counters events_per_sec()
        # subtracts so quarantined work never inflates throughput
        self._m_shed = m.counter("engine.requests.shed")
        self._m_parked_total = m.counter("engine.requests.parked")
        self._m_quarantined = m.counter("engine.requests.quarantined")
        self._m_retries = m.counter("engine.faults.chunk_retries")
        self._m_demoted = m.counter("engine.faults.backend_demoted")
        self._m_injected = m.counter("engine.faults.injected")
        # steady-state recompiles: chunk compile-cache growth beyond the
        # allowlisted sites (cold start, ring growth, demotion rebuild);
        # any increment means a shape-unstable dispatch path
        self._m_recompiles = m.counter("engine.tick.recompiles")
        self._m_q_events = m.counter("engine.episode.quarantined_events")
        self._m_q_steps = m.counter("engine.episode.quarantined_steps")
        self._m_parked_depth = m.gauge("engine.queue.parked")
        # crash-safety + preemption plane: snapshot/restore timing, the
        # corrupt-checkpoint fallback counter restore_latest_snapshot()
        # bumps, and parking-buffer traffic (park/restore cost per slot
        # in the histograms; parked_events gives the per-event divisor)
        self._m_snap_time = m.histogram(
            "engine.snapshot.save_s", lo=1e-6, hi=100.0
        )
        self._m_restore_snap_time = m.histogram(
            "engine.snapshot.restore_s", lo=1e-6, hi=100.0
        )
        self._m_ckpt_fallback = m.counter(
            "engine.faults.checkpoint_fallback"
        )
        self._m_preempt_parked = m.counter("engine.preempt.parked")
        self._m_preempt_resumed = m.counter("engine.preempt.resumed")
        self._m_preempt_events = m.counter("engine.preempt.parked_events")
        self._m_preempt_depth = m.gauge("engine.preempt.buffer_depth")
        self._m_park_time = m.histogram(
            "engine.preempt.park_s", lo=1e-7, hi=10.0
        )
        self._m_restore_time = m.histogram(
            "engine.preempt.restore_s", lo=1e-7, hi=10.0
        )
        # SLO verdict gauge (0 healthy / 1 degraded / 2 breach), written
        # by health(); readable in any snapshot without re-evaluating
        self._m_health = m.gauge("engine.slo.status")
        # windowed time series over the registry: per-tick + per-submit
        # samples; latency buckets tracked so windowed p99 (and the
        # latency SLO's fraction-over-target) reconstructs from diffs
        self.timeseries = TimeSeriesSampler(
            self.metrics,
            capacity=timeseries_capacity,
            track_buckets=("engine.request.latency_s",),
        )

    def metrics_snapshot(self) -> Dict[str, Dict]:
        """JSON-able snapshot of every engine instrument."""
        return self.metrics.snapshot()

    def export_trace(self, path) -> None:
        """Write the recorded spans as Chrome trace-event JSON
        (Perfetto-loadable)."""
        self.trace.write(path)

    def health(self) -> Dict:
        """Evaluate the engine's SLOs (multi-window burn rates over the
        time-series sampler) and publish the verdict as the
        ``engine.slo.status`` gauge.  Returns the JSON-able report:
        ``status`` is ``healthy`` / ``degraded`` / ``breach``, ``slos``
        carries per-SLO windowed error rates and per-rule burn rates."""
        report = slo_mod.evaluate(self.slos, self.timeseries)
        self._m_health.set(report["status_code"])
        report["diagnosis"] = self._diagnose(report)
        return report

    def _diagnose(self, report: Dict) -> Dict:
        """Separate *why* the SLO verdict is what it is, so an operator
        (or the serve launcher) acts on the cause, not the symptom:

        - ``faulty`` — quarantines, backend demotions, or dispatch
          retries happened: fix the fault before touching capacity.
        - ``overloaded`` — SLOs unhappy *and* the admission plane is
          actively shedding: the engine is protecting itself correctly;
          add capacity or tighten admission.
        - ``breaching`` — SLOs unhappy with no shedding and no faults:
          deadlines are simply unserveable at current throughput (or no
          admission policy is installed to shed the hopeless tail).
        - ``nominal`` — healthy.
        """
        quarantined = self._m_quarantined.value
        demoted = self._m_demoted.value
        retries = self._m_retries.value
        shed = self._m_shed.value
        recompiles = int(self._m_recompiles.value)
        window = self.timeseries.ratio(
            "engine.requests.shed", "engine.requests.submitted", 10.0
        )
        unhappy = report["status"] != "healthy"
        if quarantined > 0 or demoted > 0 or retries > 0:
            verdict = "faulty"
            hint = (
                "fault path active (quarantines/demotions/retries): "
                "inspect fault_events and engine.faults.* counters "
                "before scaling anything"
            )
        elif unhappy and shed > 0:
            verdict = "overloaded"
            hint = (
                "SLO pressure with active load shedding: the admission "
                "plane is degrading correctly — add capacity (slots/"
                "hosts) or lower the offered rate"
            )
        elif unhappy:
            verdict = "breaching"
            hint = (
                "SLO pressure with no shedding and no faults: deadlines "
                "exceed serving capacity — enable an AdmissionPolicy or "
                "relax deadline targets"
            )
        else:
            verdict = "nominal"
            hint = "no action needed"
        if recompiles > 0:
            hint += (
                "; WARNING: steady-state chunk recompiles observed "
                f"({recompiles}) — a dispatch path is shape-unstable "
                "(every compile stalls serving for the full trace+compile)"
            )
        # preemption thrash: windows are being swapped in and out faster
        # than any of them completes — the engine is busy moving state,
        # not integrating spikes
        park_rate = self.timeseries.rate("engine.preempt.parked", 10.0)
        done_rate = self.timeseries.rate("engine.requests.completed", 10.0)
        thrash = park_rate > 0.0 and park_rate > done_rate
        if thrash:
            hint += (
                "; preempt_thrash: park/restore rate exceeds the "
                "completion rate — preemption is swapping slot state "
                "faster than windows finish (add slots, damp priority "
                "spread, or loosen deadlines)"
            )
        return {
            "verdict": verdict,
            "hint": hint,
            "recompiling": recompiles > 0,
            "steady_state_recompiles": recompiles,
            "shed_total": shed,
            "windowed_shed_rate": window,
            "parked_depth": len(self._parked),
            "preempt_thrash": thrash,
            "preempt_parked_depth": len(self._preempt_parked),
            "preempt_park_rate": park_rate,
            "quarantined_total": quarantined,
            "backend_demotions": demoted,
            "chunk_retries": retries,
            "backend": self.backend,
        }

    def windowed_miss_rate(self, window_s: Optional[float] = 1.0) -> float:
        """Deadline-miss fraction of completions over the trailing
        window (whole series when ``window_s`` is None) — the evolving
        signal, vs ``deadline_miss_rate()``'s episode-lifetime average."""
        return self.timeseries.ratio(
            "engine.requests.deadline_missed",
            "engine.requests.completed",
            window_s,
        )

    # ------------------------------------------------------------- state
    def _reset_all(self) -> None:
        cfg, S = self.cfg, self.S
        self._states = self._on_slots(runtime.init_states(cfg, S))
        self._ring = self._alloc_ring(self._ring_steps)
        self._meta = self._on_slots({
            "done": jnp.zeros((S,), jnp.int32),
            "total": jnp.zeros((S,), jnp.int32),
            "admit": jnp.zeros((S,), jnp.int32),
            "fault": jnp.zeros((S,), jnp.int32),
        })
        self._slot_req = [None] * S  # request id per slot
        self._slot_parked = [False] * S  # admitted from the parked list
        self._slot_done = np.zeros(S, np.int64)  # steps dispatched
        self._slot_retired = np.zeros(S, np.int64)  # steps stats-retired
        self._slot_total = np.zeros(S, np.int64)
        self._slot_submit_t = np.zeros(S, np.float64)
        self._slot_admit_t = np.zeros(S, np.float64)
        self._slot_deadline: List[Optional[float]] = [None] * S  # absolute
        self._slot_rel_deadline: List[Optional[float]] = [None] * S
        self._slot_priority = np.zeros(S, np.int64)
        self._slot_counts = np.zeros((S, cfg.layer_sizes[-1]), np.float64)
        self._slot_memsum = np.zeros((S, cfg.layer_sizes[-1]), np.float64)
        self._slot_events = np.zeros((S, cfg.num_layers), np.float64)
        # one-deep stats-future pipeline: (stats device pytree,
        # per-slot take snapshot, per-slot request-id snapshot)
        self._inflight: "collections.deque[Tuple]" = collections.deque()
        self._queue: List[tuple] = []  # heap: (key, rid, req, t_sub, dl)
        # fault-tolerance plane: parked priority requests (FIFO, served
        # best-effort when the heap empties), shed/quarantined results
        # awaiting delivery by poll(), the quarantine log (joined by the
        # bench's recovery-ticks metric), and the tick index the log and
        # injector schedules are expressed in
        self._parked: "collections.deque[tuple]" = collections.deque()
        # preemption parking buffer: host-side records of displaced
        # mid-window slots (state rows + ring row + accumulators),
        # resumed by _fill_slot in urgency order
        self._preempt_parked: List[Dict] = []
        self._pending_results: List[StreamResult] = []
        self.fault_events: List[Dict] = []
        self._tick_index = 0
        self._seq = 0
        self._next_rid = 0
        self._episode_open = False
        self._episode_t0 = 0.0
        self.metrics.reset(prefix="engine.episode.")
        self.metrics.reset(prefix="engine.tick.")

    def _begin_episode(self, now: float) -> None:
        # throughput + deadline counters are per-episode: an episode opens
        # at the first submit on an idle engine and closes when the last
        # queued request drains (see events_per_sec for the denominator).
        # wall_s resets here too — it used to survive from the previous
        # episode, so a mid-episode read mixed a stale denominator with
        # fresh numerators (tests/test_snn_engine.py pins the fix).
        self.metrics.reset(prefix="engine.episode.")
        self._episode_t0 = now
        self._episode_open = True

    # episode counters read straight from the registry; properties keep
    # the pre-obs attribute API (and make stray writes fail loudly)
    @property
    def total_events(self) -> float:
        return self._m_events.value

    @property
    def total_steps(self) -> int:
        return int(self._m_steps.value)

    @property
    def completed(self) -> int:
        return int(self._m_completed.value)

    @property
    def deadline_misses(self) -> int:
        return int(self._m_misses.value)

    @property
    def wall_s(self) -> float:
        return self._m_wall.value

    # --------------------------------------------------------- admission
    def _resolve_steps(self, req: StreamRequest) -> int:
        # explicit None check: ``req.num_steps or cfg.num_steps`` silently
        # treated num_steps=0 as unset
        T = (
            self.cfg.num_steps
            if req.num_steps is None
            else int(req.num_steps)
        )
        if T < 1:
            raise ValueError(f"num_steps must be >= 1, got {req.num_steps}")
        return T

    def submit(self, req: StreamRequest) -> int:
        """Enqueue one request; returns its request id.

        Admission happens at the next ``poll()``: free slots are filled in
        (priority desc, earliest deadline, FIFO) order, so a later submit
        with a tighter deadline overtakes queued work it never saw.
        """
        with self.trace.phase("engine.submit", track="queue"):
            T = self._resolve_steps(req)
            K = self.cfg.layer_sizes[0]
            if req.spikes is not None:
                shape = tuple(np.shape(req.spikes))
                if shape != (T, K):
                    raise ValueError(
                        f"request spikes shape {shape} != ({T}, {K})"
                    )
                # staged device-side as int8 event values: trains must be
                # integer-valued spike magnitudes (all our encoders are)
                s = np.asarray(req.spikes)
                if not np.all(np.isfinite(s)):
                    raise ValueError(
                        "request spikes contain NaN/inf — non-finite trains "
                        "are rejected at the admission boundary"
                    )
                if not np.all((s == np.round(s)) & (np.abs(s) <= 127)):
                    raise ValueError(
                        "request spikes must be integer-valued magnitudes in "
                        "[-127, 127] (e.g. {0,1} rate codes, {-1,0,1} DVS "
                        "polarities) — the train is staged as an int8 AER "
                        "event table"
                    )
            elif req.image is not None:
                shape = tuple(np.shape(req.image))
                if shape != (K,):
                    raise ValueError(f"request image shape {shape} != ({K},)")
                # contents matter, not just shape: a NaN pixel makes
                # rate_encode (uniform < NaN is always False) emit an
                # all-zero train — a silently wrong answer, not a crash —
                # so non-finite images are rejected here at the boundary
                # (tests/test_faults.py pins the silent-garbage failure)
                img = np.asarray(req.image)
                if not np.all(np.isfinite(img)):
                    raise ValueError(
                        "request image contains NaN/inf — non-finite images "
                        "are rejected at the admission boundary"
                    )
            else:
                raise ValueError("StreamRequest needs image or spikes")
            now = time.perf_counter()
            if not self._episode_open:
                self._begin_episode(now)
            rid = self._next_rid
            self._next_rid += 1
            dl = now + req.deadline_s if req.deadline_s is not None else None
            self._m_submitted.inc()
            if self.admission is not None:
                verdict, reason = shed_mod.backpressure(
                    self.admission,
                    queue_depth=len(self._queue),
                    parked_depth=len(self._parked),
                    priority=req.priority,
                )
                if verdict == shed_mod.SHED:
                    self._shed(rid, req, now, dl, reason)
                    self._sample()
                    return rid
                if verdict == shed_mod.PARK:
                    self._park(rid, req, now, dl, reason)
                    self._sample()
                    return rid
            key = (
                -int(req.priority),
                0 if dl is not None else 1,  # deadline-less requests last
                dl if dl is not None else 0.0,
                self._seq,  # FIFO tiebreak; also keeps heap entries orderable
            )
            self._seq += 1
            heapq.heappush(self._queue, (key, rid, req, now, dl))
            self._m_qdepth.set(len(self._queue))
            self.trace.instant(
                "submit", now, track="queue",
                args={"rid": rid, "priority": req.priority},
            )
            # admission is a state change worth a time-series point (queue
            # depth, submitted counter) even between ticks
            self._sample()
            return rid

    def _sample(self) -> None:
        with self.trace.phase("engine.sample", track="engine"):
            self.timeseries.sample()

    def _admit(
        self,
        s: int,
        rid: int,
        req: StreamRequest,
        t_submit: float,
        abs_deadline: Optional[float],
    ) -> None:
        T = self._resolve_steps(req)
        track = f"slot{s}"
        with self.trace.phase(
            "engine.admit", track=track, args={"rid": rid, "steps": T}
        ) as admit:
            # time queued: submit -> admission start, on the queue track
            self.trace.span(
                "queue", t_submit, admit.t0, track="queue",
                args={"rid": rid, "priority": req.priority},
            )
            if T > self._ring_steps:
                self._grow_ring(T)
            # every admission upload is *explicit* (device_put), so the
            # whole serving loop — not just steady-state ticks — runs
            # clean under jax.transfer_guard("disallow"); a dense train
            # is uploaded once as (T, K) and compressed to the packed
            # event table on device
            with self.trace.phase("engine.admit.upload", track=track):
                slot = jax.device_put(np.int32(s))
                if req.spikes is not None:
                    x = jax.device_put(np.asarray(req.spikes, np.float32))
                else:
                    self._rng, k = jax.random.split(self._rng)
                    x = jax.device_put(np.asarray(req.image, np.float32))
            with self.trace.phase("engine.admit.dispatch", track=track):
                if req.spikes is not None:
                    self._ring, self._meta = self._admit_spikes_fn(
                        self._ring, self._meta, x, slot
                    )
                else:
                    self._ring, self._meta = self._admit_image_fn(
                        self._ring, self._meta, x, k, slot, T=T
                    )
            self._slot_req[s] = rid
            self._slot_done[s] = 0
            self._slot_retired[s] = 0
            self._slot_total[s] = T
            self._slot_submit_t[s] = t_submit
            # queue_wait_s keeps its pre-obs meaning: submit ->
            # admission complete, staging included
            self._slot_admit_t[s] = time.perf_counter()
        self._m_qwait.record(self._slot_admit_t[s] - t_submit)
        self._slot_deadline[s] = abs_deadline
        self._slot_rel_deadline[s] = req.deadline_s
        self._slot_priority[s] = int(req.priority)
        self._slot_counts[s] = 0.0
        self._slot_memsum[s] = 0.0
        self._slot_events[s] = 0.0

    # --------------------------------------------------- admission plane
    def _void_result(
        self,
        rid: int,
        req: StreamRequest,
        t_submit: float,
        *,
        disposition: str,
        fault: Optional[str],
    ) -> StreamResult:
        """A result that carries a disposition instead of an inference:
        no prediction, no stats, no deadline verdict (the request was
        never served, so it neither met nor missed anything)."""
        cfg = self.cfg
        now = time.perf_counter()
        return StreamResult(
            request_id=rid,
            prediction=-1,
            spike_counts=np.zeros(cfg.layer_sizes[-1]),
            steps=self._resolve_steps(req),
            latency_s=now - t_submit,
            queue_wait_s=now - t_submit,
            events_per_layer=np.zeros(cfg.num_layers),
            spike_rate=0.0,
            energy_pj=0.0,
            deadline_s=req.deadline_s,
            deadline_missed=False,
            disposition=disposition,
            fault=fault,
        )

    def _shed(
        self,
        rid: int,
        req: StreamRequest,
        t_submit: float,
        abs_deadline: Optional[float],
        reason: str,
    ) -> None:
        self._m_shed.inc()
        self.trace.instant(
            "shed", time.perf_counter(), track="queue",
            args={"rid": rid, "reason": reason},
        )
        self._pending_results.append(self._void_result(
            rid, req, t_submit, disposition="shed", fault=reason
        ))

    def _park(
        self,
        rid: int,
        req: StreamRequest,
        t_submit: float,
        abs_deadline: Optional[float],
        reason: str,
    ) -> None:
        self._m_parked_total.inc()
        self._parked.append((rid, req, t_submit, abs_deadline))
        self._m_parked_depth.set(len(self._parked))
        self.trace.instant(
            "park", time.perf_counter(), track="queue",
            args={"rid": rid, "reason": reason},
        )

    def measured_ticks_per_s(
        self, window_s: Optional[float] = None
    ) -> float:
        """Tick throughput off the time-series sampler (trailing
        ``window_s``, falling back to the whole series when the window
        saw no flow) — the evidence the feasibility shedder converts
        into a completion-time lower bound.  0.0 on a cold engine."""
        key = "engine.tick.dispatch_s.count"
        r = self.timeseries.rate(key, window_s)
        if r <= 0.0:
            r = self.timeseries.rate(key, None)
        return r

    def _admission_verdict(
        self, req: StreamRequest, abs_deadline: Optional[float]
    ) -> Tuple[str, Optional[str]]:
        """Feasibility check when a queued request wins a free slot."""
        if self.admission is None or not self.admission.shed_unmeetable:
            return shed_mod.ADMIT, None
        return shed_mod.feasibility(
            self.admission,
            steps=self._resolve_steps(req),
            chunk_steps=self.Tc,
            deadline_abs=abs_deadline,
            now=time.perf_counter(),
            ticks_per_s=self.measured_ticks_per_s(
                self.admission.rate_window_s
            ),
            priority=req.priority,
        )

    # -------------------------------------------------------------- tick
    def _tick(self) -> List[int]:
        """One pipelined engine step: dispatch the next chunk (if any slot
        has steps left) and retire completed chunks' stats; returns the
        slots whose requests finished.

        A steady mid-window tick performs no H2D transfer — the chunk
        consumes only device-resident buffers — and exactly one D2H
        transfer, the explicit ``device_get`` of the retired chunk's
        reduced stats.  A tick whose dispatch completes some request's
        window drains the stats queue eagerly (trading that tick's
        overlap for the request's completion latency and an accurate
        deadline verdict).
        """
        S, Tc = self.S, self.Tc
        tick = self._tick_index
        self._tick_index += 1
        if self.injector is not None:
            applied = self.injector.begin_tick(self, tick)
            if applied:
                self._m_injected.inc(len(applied))
            if self.injector.stalled(tick):
                # injected stall: the tick makes no progress at all —
                # exactly the wedge drain(timeout_s=...) must survive
                return []
        with self.trace.phase("engine.tick.prep", track="tick") as prep:
            take = np.zeros(S, np.int32)
            for s in range(S):
                if self._slot_req[s] is None:
                    continue
                take[s] = min(
                    Tc, int(self._slot_total[s]) - int(self._slot_done[s])
                )
            dispatched = bool(take.sum() > 0)
        dispatch_s = 0.0
        if dispatched:
            with self.trace.phase(
                "engine.tick.dispatch", track="tick"
            ) as disp:
                self._states, self._meta, stats_dev = self._dispatch_chunk()
                self._slot_done += take
                self._inflight.append(
                    (stats_dev, take.copy(), list(self._slot_req))
                )
                self._note_chunk_compiles()
            dispatch_s = disp.duration_s
        t2 = time.perf_counter()
        finished: List[int] = []
        # keep at most pipeline_depth chunks' stats in flight; when
        # nothing was dispatched, retire one anyway so poll() always
        # makes progress.  Eagerly drain when a request's *final* chunk
        # is in flight (all its steps dispatched, not yet retired): its
        # completion — and deadline verdict — should not wait one more
        # poll round.  Steady mid-window ticks keep the full overlap;
        # only finishing ticks synchronize.
        finishing = any(
            self._slot_req[s] is not None
            and self._slot_done[s] >= self._slot_total[s]
            and self._slot_retired[s] < self._slot_total[s]
            for s in range(S)
        )
        force = 0 if dispatched else min(1, len(self._inflight))
        while self._inflight and (
            len(self._inflight) > self.pipeline_depth or force or finishing
        ):
            force = 0
            finished.extend(self._retire())
        t3 = time.perf_counter()
        # tick-phase histograms keep exact sum/count (the tick_breakdown
        # means) plus tail percentiles, from the phases' own timestamps
        self._m_prep.record(prep.duration_s)
        self._m_dispatch.record(dispatch_s)
        self._m_fetch.record(t3 - t2)
        self._m_active.set(sum(r is not None for r in self._slot_req))
        return finished

    def _note_chunk_compiles(self) -> None:
        """Fold chunk compile-cache growth beyond the allowlisted sites
        (cold start, ring growth, demotion rebuild) into the
        ``engine.tick.recompiles`` counter — the repro-lint recompile
        contract (``repro.analysis.contracts.RecompileDetector`` wraps
        the same signal for tests/benchmarks)."""
        get = getattr(self._chunk, "_cache_size", None)
        if get is None:
            return
        try:
            size = int(get())
        except Exception:
            return
        extra = size - self._chunk_compiles_expected
        if extra > self._chunk_compiles_accounted:
            self._m_recompiles.inc(extra - self._chunk_compiles_accounted)
            self._chunk_compiles_accounted = extra

    def steady_state_recompiles(self) -> int:
        """Chunk recompiles beyond the known compile sites (lifetime);
        nonzero means some dispatch path is shape-unstable."""
        return int(self._m_recompiles.value)

    def _dispatch_chunk(self):
        """One supervised chunk dispatch: injected faults raise before
        the jitted call (so the donated states/meta buffers are still
        valid on retry), transient failures retry with capped backoff,
        and persistent fused failures demote the engine to the jnp
        reference chunk permanently (rebuilding the compiled pair) —
        see ``repro.faults.supervisor``."""
        def attempt():
            if self.injector is not None:
                self.injector.maybe_raise(self.backend)
            return self._chunk(
                self._prepared, self._states, self._ring, self._meta
            )

        def demote():
            self.backend = "jnp"
            self._chunk, self._chunk_nodonate = self._build_chunk("jnp")
            # fresh jit object: its cold-start compile is a known site
            self._chunk_compiles_expected = 1
            self._chunk_compiles_accounted = 0
            return attempt

        return self._supervisor.call(
            attempt,
            backend=self.backend,
            demote=demote if self.backend == "fused" else None,
        )

    def _retire(self) -> List[int]:
        """Fetch the oldest in-flight chunk's stats (the tick's single
        D2H transfer) and fold them into per-slot accumulators."""
        with self.trace.phase("engine.retire", track="tick"):
            stats_dev, take, rids = self._inflight.popleft()
            with self.trace.phase("engine.retire.fetch", track="tick"):
                stats = jax.device_get(stats_dev)
            fault = stats.get("fault")
            finished = []
            for s in range(self.S):
                if rids[s] is None or take[s] == 0:
                    continue
                if self._slot_req[s] != rids[s]:
                    continue  # slot was freed and re-admitted since dispatch
                if fault is not None and int(fault[s]) != 0:
                    # poisoned slot: discard this chunk's stats (they may be
                    # NaN), fail the request into a quarantined result, and
                    # free the slot — the other S-1 slots fold normally and
                    # the in-graph sanitization already cleaned the state
                    self._quarantine(s, int(fault[s]))
                    continue
                self._slot_counts[s] += stats["counts"][s]
                self._slot_memsum[s] += stats["memsum"][s]
                self._slot_events[s] += stats["events"][s]
                self._slot_retired[s] += int(take[s])
                self._m_events.inc(float(stats["events"][s].sum()))
                self._m_steps.inc(int(take[s]))
                if self._slot_retired[s] >= self._slot_total[s]:
                    finished.append(s)
            return finished

    def _quarantine(self, s: int, code: int) -> None:
        """Fail slot ``s``'s request into a quarantined result and free
        the slot.  The request is *not* a completion: it leaves the
        completed/deadline-miss accounting untouched (documented
        denominator policy on ``deadline_miss_rate``), and the work it
        already folded is moved to the quarantined-exclusion counters so
        ``events_per_sec()`` stays honest."""
        rid = self._slot_req[s]
        names = fault_code_names(code)
        now = time.perf_counter()
        self._m_q_events.inc(float(self._slot_events[s].sum()))
        self._m_q_steps.inc(float(self._slot_retired[s]))
        self._m_quarantined.inc()
        self.fault_events.append({
            "tick": self._tick_index,
            "slot": s,
            "rid": rid,
            "code": code,
            "fault": names,
        })
        self.trace.instant(
            "quarantine", now, track=f"slot{s}",
            args={"rid": rid, "fault": names},
        )
        self._pending_results.append(StreamResult(
            request_id=rid,
            prediction=-1,
            spike_counts=np.zeros(self.cfg.layer_sizes[-1]),
            steps=int(self._slot_total[s]),
            latency_s=now - self._slot_submit_t[s],
            queue_wait_s=self._slot_admit_t[s] - self._slot_submit_t[s],
            events_per_layer=np.zeros(self.cfg.num_layers),
            spike_rate=0.0,
            energy_pj=0.0,
            deadline_s=self._slot_rel_deadline[s],
            deadline_missed=False,
            disposition="quarantined",
            fault=names,
            parked=self._slot_parked[s],
        ))
        self._slot_req[s] = None
        self._slot_parked[s] = False

    def _finalize(self, s: int) -> StreamResult:
        with self.trace.phase("engine.finalize", track=f"slot{s}"):
            cfg = self.cfg
            T = int(self._slot_total[s])
            ev = self._slot_events[s].copy()
            oc = energy.snn_ops_from_events(
                cfg.layer_sizes, T, ev, neuron_kind=cfg.neuron_kind
            )
            counts = self._slot_counts[s]
            pred = int(np.argmax(counts + 1e-6 * self._slot_memsum[s]))
            finish_t = time.perf_counter()
            dl = self._slot_deadline[s]
            missed = dl is not None and finish_t > dl
            self._m_completed.inc()
            self._m_finished.inc()
            if missed:
                self._m_misses.inc()
                self._m_missed_total.inc()
            latency_s = finish_t - self._slot_submit_t[s]
            self._m_latency.record(latency_s)
            self._m_energy.record(oc.energy_pj())
            # the slot's occupancy, admission end -> completion
            self.trace.span(
                "occupy", float(self._slot_admit_t[s]), finish_t,
                track=f"slot{s}",
                args={"rid": self._slot_req[s], "steps": T},
            )
            self.trace.instant(
                "complete", finish_t, track=f"slot{s}",
                args={
                    "rid": self._slot_req[s],
                    "latency_ms": latency_s * 1e3,
                    "energy_pj": oc.energy_pj(),
                    "deadline_missed": bool(missed),
                },
            )
            res = StreamResult(
                request_id=self._slot_req[s],
                prediction=pred,
                spike_counts=counts.copy(),
                steps=T,
                latency_s=latency_s,
                queue_wait_s=self._slot_admit_t[s] - self._slot_submit_t[s],
                events_per_layer=ev,
                spike_rate=float(ev[0] / (T * cfg.layer_sizes[0])),
                energy_pj=oc.energy_pj(),
                deadline_s=self._slot_rel_deadline[s],
                deadline_missed=missed,
                parked=self._slot_parked[s],
            )
            self._slot_req[s] = None
            self._slot_parked[s] = False
            return res

    # -------------------------------------------------------- preemption
    def _drain_inflight(self) -> None:
        """Retire every pipelined chunk's stats, finalizing any
        requests they complete into the pending-results buffer — the
        consistency point snapshot() and preemption parking require:
        afterwards ``_slot_retired == _slot_done`` for every resident
        slot, so parked/persisted host accumulators match the device
        state exactly."""
        while self._inflight:
            for s in self._retire():
                self._pending_results.append(self._finalize(s))

    def _slot_key(self, s: int):
        """Urgency key of slot ``s``'s resident request — comparable
        with the admission heap's key prefix (priority desc,
        deadline-less last, EDF)."""
        dl = self._slot_deadline[s]
        return (
            -int(self._slot_priority[s]),
            0 if dl is not None else 1,
            dl if dl is not None else 0.0,
        )

    def _best_preempt_key(self) -> Optional[Tuple]:
        """(key, index) of the most urgent preempt-parked window, or
        None when the parking buffer is empty."""
        best = None
        for i, rec in enumerate(self._preempt_parked):
            dl = rec["abs_deadline"]
            k = (
                -int(rec["priority"]),
                0 if dl is not None else 1,
                dl if dl is not None else 0.0,
            )
            if best is None or k < best[0]:
                best = (k, i)
        return best

    def _victim(self, head_key) -> Optional[int]:
        """The loosest-urgency resident slot *strictly* looser than
        ``head_key``, or None — an equal-urgency arrival never
        displaces a running window (ties would swap-thrash)."""
        worst, worst_key = None, None
        for s in range(self.S):
            if self._slot_req[s] is None:
                continue
            k = self._slot_key(s)
            if worst_key is None or k > worst_key:
                worst, worst_key = s, k
        if worst is None or not (head_key < worst_key):
            return None
        return worst

    def _maybe_preempt(self) -> None:
        """Park the loosest resident window when the queue head is
        strictly more urgent and no slot is free (``preempt=True``
        only).  At most one park per poll round — the freed slot is
        filled with the urgent request in the same round."""
        if not self.preempt or not self._queue:
            return
        if any(r is None for r in self._slot_req):
            return  # a free slot serves the arrival without displacement
        head_key = self._queue[0][0][:3]
        if self._victim(head_key) is None:
            return
        # retire pipelined stats before parking: retirement may complete
        # a slot outright (cheaper than a park/restore round trip), and
        # parking requires retired == done — a parked slot with a chunk
        # still in flight would silently drop that chunk's stats at
        # _retire()'s slot-reuse guard
        self._drain_inflight()
        if any(r is None for r in self._slot_req):
            return
        v = self._victim(head_key)
        if v is not None:
            self._park_slot(v)

    def _park_slot(self, s: int) -> None:
        """Preempt slot ``s``: move its membrane/refractory rows,
        staged ring row, scheduling metadata, and host accumulators
        into the parking buffer and free the slot.  Inverse of
        ``_resume_slot``; the round trip is bit-exact (float32/int8
        rows survive device_get/device_put unchanged).  Caller must
        have drained the stats pipeline first."""
        t0 = time.perf_counter()
        rid = self._slot_req[s]
        rec = {
            "rid": rid,
            "priority": int(self._slot_priority[s]),
            "done": int(self._slot_retired[s]),
            "total": int(self._slot_total[s]),
            "parked": bool(self._slot_parked[s]),
            "ring_steps": self._ring_steps,
            "rel_deadline": self._slot_rel_deadline[s],
            "abs_deadline": self._slot_deadline[s],
            "t_submit": float(self._slot_submit_t[s]),
            "t_admit": float(self._slot_admit_t[s]),
            "u": [_host_row(st.u, s) for st in self._states],
            "refrac": [_host_row(st.refrac, s) for st in self._states],
            "ring_addrs": _host_row(self._ring["addrs"], s),
            "ring_values": _host_row(self._ring["values"], s),
            "ring_counts": _host_row(self._ring["counts"], s),
            "counts": self._slot_counts[s].copy(),
            "memsum": self._slot_memsum[s].copy(),
            "events": self._slot_events[s].copy(),
        }
        self._preempt_parked.append(rec)
        # free the slot: total=0 makes the next chunk take nothing from
        # it; the stale device state is dead weight until overwritten
        self._meta = {
            k: self._put_row(v, s, np.int32(0)) for k, v in self._meta.items()
        }
        self._slot_req[s] = None
        self._slot_parked[s] = False
        t1 = time.perf_counter()
        self._m_preempt_parked.inc()
        self._m_preempt_events.inc(float(rec["events"].sum()))
        self._m_park_time.record(t1 - t0)
        self._m_preempt_depth.set(len(self._preempt_parked))
        self.trace.span(
            "park", t0, t1, track=f"slot{s}",
            args={"rid": rid, "done": rec["done"], "total": rec["total"]},
        )

    def _resume_slot(self, s: int, rec: Dict) -> None:
        """Admit a preempt-parked window into free slot ``s``,
        restoring its state/ring rows device-side.  The admit flag
        stays 0 — unlike fresh admission, the chunk must NOT zero the
        restored membranes — so the window continues from exactly the
        step it was parked at."""
        t0 = time.perf_counter()
        if rec["ring_steps"] > self._ring_steps:
            # the ring shrank relative to the record only across a
            # restore onto a smaller-ring engine; grow back so the
            # stored row fits (one allowlisted recompile)
            self._grow_ring(rec["ring_steps"])
        put = self._put_row
        self._states = [
            neuron.NeuronState(
                u=put(st.u, s, rec["u"][i]),
                refrac=put(st.refrac, s, rec["refrac"][i]),
            )
            for i, st in enumerate(self._states)
        ]
        # the stored ring row may be shorter than the current ring: it
        # fills the row's first steps
        self._ring = {
            k: put(v, s, rec[f"ring_{k}"]) for k, v in self._ring.items()
        }
        self._meta = {
            "done": put(self._meta["done"], s, np.int32(rec["done"])),
            "total": put(self._meta["total"], s, np.int32(rec["total"])),
            "admit": put(self._meta["admit"], s, np.int32(0)),
            "fault": put(self._meta["fault"], s, np.int32(0)),
        }
        self._slot_req[s] = rec["rid"]
        self._slot_parked[s] = rec["parked"]
        self._slot_priority[s] = rec["priority"]
        self._slot_done[s] = rec["done"]
        self._slot_retired[s] = rec["done"]
        self._slot_total[s] = rec["total"]
        self._slot_submit_t[s] = rec["t_submit"]
        self._slot_admit_t[s] = rec["t_admit"]
        self._slot_deadline[s] = rec["abs_deadline"]
        self._slot_rel_deadline[s] = rec["rel_deadline"]
        self._slot_counts[s] = rec["counts"]
        self._slot_memsum[s] = rec["memsum"]
        self._slot_events[s] = rec["events"]
        t1 = time.perf_counter()
        self._m_preempt_resumed.inc()
        self._m_restore_time.record(t1 - t0)
        self._m_preempt_depth.set(len(self._preempt_parked))
        self.trace.span(
            "resume", t0, t1, track=f"slot{s}",
            args={
                "rid": rec["rid"],
                "done": rec["done"],
                "total": rec["total"],
            },
        )

    # --------------------------------------------------- crash-safe state
    def snapshot(self, path: str) -> str:
        """Serialize the engine's complete serving state into the
        directory ``path``: per-slot membrane/refractory states, packed
        AER rings, on-device scheduling metadata, host bookkeeping, the
        admission queue, parked requests, the preemption parking
        buffer, undelivered results, the PRNG key, and the fault-event
        log.  Atomic (tmp-dir + rename + per-array crc32 checksums via
        the checkpoint plane) — a crash mid-snapshot leaves the
        previous snapshot intact.

        Wall-clock state is persisted as remaining deadline budgets and
        ages: absolute ``perf_counter`` values are meaningless in
        another process, so :meth:`restore` re-anchors them.  Restoring
        on a freshly built engine (identical params/config) finishes
        every in-flight window bit-exactly."""
        t0 = time.perf_counter()
        # consistency point: retire all pipelined stats (finalizing any
        # windows they complete) so host accumulators match device state
        self._drain_inflight()
        now = time.perf_counter()
        arrays: Dict[str, np.ndarray] = {}
        for i, st in enumerate(self._states):
            arrays[f"state{i}_u"] = np.asarray(jax.device_get(st.u))
            arrays[f"state{i}_refrac"] = np.asarray(
                jax.device_get(st.refrac)
            )
        for k, v in self._ring.items():
            arrays[f"ring_{k}"] = np.asarray(jax.device_get(v))
        for k, v in self._meta.items():
            arrays[f"meta_{k}"] = np.asarray(jax.device_get(v))
        arrays["rng_key"] = np.asarray(jax.device_get(self._rng))
        for name in ("done", "retired", "total", "priority"):
            arrays[f"slot_{name}"] = getattr(self, f"_slot_{name}").copy()
        arrays["slot_counts"] = self._slot_counts.copy()
        arrays["slot_memsum"] = self._slot_memsum.copy()
        arrays["slot_events"] = self._slot_events.copy()
        slots = []
        for s in range(self.S):
            dl = self._slot_deadline[s]
            slots.append({
                "rid": self._slot_req[s],
                "parked": bool(self._slot_parked[s]),
                "rel_deadline": self._slot_rel_deadline[s],
                "deadline_remaining_s": (
                    None if dl is None else dl - now
                ),
                "submit_age_s": now - float(self._slot_submit_t[s]),
                "admit_age_s": now - float(self._slot_admit_t[s]),
            })

        def pack_req(prefix, rid, req, t_sub, dl, extra=None):
            if req.spikes is not None:
                arrays[f"{prefix}_spikes"] = np.asarray(req.spikes)
            else:
                arrays[f"{prefix}_image"] = np.asarray(req.image)
            doc = {
                "rid": rid,
                "priority": int(req.priority),
                "num_steps": req.num_steps,
                "deadline_s": req.deadline_s,
                "submit_age_s": now - t_sub,
                "deadline_remaining_s": (
                    None if dl is None else dl - now
                ),
            }
            doc.update(extra or {})
            return doc

        queue_docs = [
            pack_req(f"q{i}", rid, req, t_sub, dl, {"seq": key[3]})
            for i, (key, rid, req, t_sub, dl)
            in enumerate(sorted(self._queue))
        ]
        parked_docs = [
            pack_req(f"p{i}", rid, req, t_sub, dl)
            for i, (rid, req, t_sub, dl) in enumerate(self._parked)
        ]
        pp_docs = []
        for i, rec in enumerate(self._preempt_parked):
            for layer in range(len(rec["u"])):
                arrays[f"pp{i}_u{layer}"] = rec["u"][layer]
                arrays[f"pp{i}_refrac{layer}"] = rec["refrac"][layer]
            for k in ("ring_addrs", "ring_values", "ring_counts",
                      "counts", "memsum", "events"):
                arrays[f"pp{i}_{k}"] = rec[k]
            dl = rec["abs_deadline"]
            pp_docs.append({
                "rid": rec["rid"],
                "priority": rec["priority"],
                "done": rec["done"],
                "total": rec["total"],
                "parked": rec["parked"],
                "ring_steps": rec["ring_steps"],
                "rel_deadline": rec["rel_deadline"],
                "deadline_remaining_s": (
                    None if dl is None else dl - now
                ),
                "submit_age_s": now - rec["t_submit"],
                "admit_age_s": now - rec["t_admit"],
            })
        manifest = {
            "kind": "snn_engine_snapshot",
            "geometry": {
                "num_slots": self.S,
                "chunk_steps": self.Tc,
                "event_capacity": self.C,
                "ring_steps": self._ring_steps,
                "layer_sizes": list(self.cfg.layer_sizes),
            },
            "backend": self.backend,
            "tick_index": self._tick_index,
            "seq": self._seq,
            "next_rid": self._next_rid,
            "snap_index": self._snap_index,
            "episode_open": self._episode_open,
            "episode_age_s": (
                now - self._episode_t0 if self._episode_open else 0.0
            ),
            "slots": slots,
            "queue": queue_docs,
            "parked": parked_docs,
            "preempt_parked": pp_docs,
            "pending_results": [
                _doc_result(r) for r in self._pending_results
            ],
            "fault_events": list(self.fault_events),
        }
        path = os.path.normpath(path)
        out = publish_array_dir(
            os.path.dirname(path) or ".",
            os.path.basename(path),
            arrays,
            manifest,
        )
        t1 = time.perf_counter()
        self._m_snap_time.record(t1 - t0)
        self.trace.span(
            "snapshot", t0, t1, track="engine", args={"path": out}
        )
        return out

    def restore(self, path: str) -> None:
        """Load a snapshot written by :meth:`snapshot` into this engine
        (freshly constructed with the same params/config).  Raises
        :class:`~repro.checkpoint.CheckpointCorruptError` when the
        snapshot fails checksum/read verification, ValueError on a
        geometry mismatch (different slots/chunk/capacity/layers —
        snapshots are elastic across *mesh* shape, not model shape)."""
        t_start = time.perf_counter()
        path = os.path.normpath(path)
        arrays, manifest = load_array_dir(path)
        if manifest.get("kind") != "snn_engine_snapshot":
            raise ValueError(f"{path} is not an engine snapshot")
        g = manifest["geometry"]
        want = {
            "num_slots": self.S,
            "chunk_steps": self.Tc,
            "event_capacity": self.C,
            "layer_sizes": list(self.cfg.layer_sizes),
        }
        got = {k: g.get(k) for k in want}
        if got != want:
            raise ValueError(
                f"snapshot geometry mismatch: snapshot {got} != "
                f"engine {want}"
            )
        self._reset_all()
        if int(g["ring_steps"]) != self._ring_steps:
            self._ring_steps = int(g["ring_steps"])
            # a different ring shape is a fresh compile site for this
            # engine's chunk — allowlist it
            self._chunk_compiles_expected += 1
        now = time.perf_counter()
        try:
            self._states = self._on_slots([
                neuron.NeuronState(
                    u=arrays[f"state{i}_u"],
                    refrac=arrays[f"state{i}_refrac"],
                )
                for i in range(len(self._states))
            ])
            self._ring = self._on_slots({
                k: arrays[f"ring_{k}"] for k in ("addrs", "values", "counts")
            })
            self._meta = self._on_slots({
                k: arrays[f"meta_{k}"]
                for k in ("done", "total", "admit", "fault")
            })
            self._rng = jax.device_put(arrays["rng_key"])
            self._slot_done = arrays["slot_done"].astype(np.int64)
            self._slot_retired = arrays["slot_retired"].astype(np.int64)
            self._slot_total = arrays["slot_total"].astype(np.int64)
            self._slot_priority = arrays["slot_priority"].astype(
                np.int64
            )
            self._slot_counts = arrays["slot_counts"].astype(np.float64)
            self._slot_memsum = arrays["slot_memsum"].astype(np.float64)
            self._slot_events = arrays["slot_events"].astype(np.float64)
            for s, doc in enumerate(manifest["slots"]):
                self._slot_req[s] = doc["rid"]
                self._slot_parked[s] = bool(doc["parked"])
                self._slot_rel_deadline[s] = doc["rel_deadline"]
                rem = doc["deadline_remaining_s"]
                self._slot_deadline[s] = (
                    None if rem is None else now + rem
                )
                self._slot_submit_t[s] = now - doc["submit_age_s"]
                self._slot_admit_t[s] = now - doc["admit_age_s"]

            def unpack_req(prefix, doc):
                kw = dict(
                    num_steps=doc["num_steps"],
                    deadline_s=doc["deadline_s"],
                    priority=doc["priority"],
                )
                if f"{prefix}_spikes" in arrays:
                    req = StreamRequest(
                        spikes=arrays[f"{prefix}_spikes"], **kw
                    )
                else:
                    req = StreamRequest(
                        image=arrays[f"{prefix}_image"], **kw
                    )
                rem = doc["deadline_remaining_s"]
                dl = None if rem is None else now + rem
                return req, now - doc["submit_age_s"], dl

            self._queue = []
            for i, doc in enumerate(manifest["queue"]):
                req, t_sub, dl = unpack_req(f"q{i}", doc)
                key = (
                    -int(req.priority),
                    0 if dl is not None else 1,
                    dl if dl is not None else 0.0,
                    doc["seq"],
                )
                heapq.heappush(
                    self._queue, (key, doc["rid"], req, t_sub, dl)
                )
            self._parked = collections.deque()
            for i, doc in enumerate(manifest["parked"]):
                req, t_sub, dl = unpack_req(f"p{i}", doc)
                self._parked.append((doc["rid"], req, t_sub, dl))
            self._preempt_parked = []
            n_layers = len(self._states)
            for i, doc in enumerate(manifest["preempt_parked"]):
                rem = doc["deadline_remaining_s"]
                self._preempt_parked.append({
                    "rid": doc["rid"],
                    "priority": int(doc["priority"]),
                    "done": int(doc["done"]),
                    "total": int(doc["total"]),
                    "parked": bool(doc["parked"]),
                    "ring_steps": int(doc["ring_steps"]),
                    "rel_deadline": doc["rel_deadline"],
                    "abs_deadline": (
                        None if rem is None else now + rem
                    ),
                    "t_submit": now - doc["submit_age_s"],
                    "t_admit": now - doc["admit_age_s"],
                    "u": [
                        arrays[f"pp{i}_u{layer}"]
                        for layer in range(n_layers)
                    ],
                    "refrac": [
                        arrays[f"pp{i}_refrac{layer}"]
                        for layer in range(n_layers)
                    ],
                    "ring_addrs": arrays[f"pp{i}_ring_addrs"],
                    "ring_values": arrays[f"pp{i}_ring_values"],
                    "ring_counts": arrays[f"pp{i}_ring_counts"],
                    "counts": arrays[f"pp{i}_counts"],
                    "memsum": arrays[f"pp{i}_memsum"],
                    "events": arrays[f"pp{i}_events"],
                })
        except KeyError as e:
            raise CheckpointCorruptError(
                f"array {e} missing from snapshot {path}"
            ) from e
        self._pending_results = [
            _undoc_result(d) for d in manifest["pending_results"]
        ]
        self.fault_events = list(manifest["fault_events"])
        self._tick_index = int(manifest["tick_index"])
        self._seq = int(manifest["seq"])
        self._next_rid = int(manifest["next_rid"])
        self._snap_index = int(manifest.get("snap_index", 0))
        self._m_qdepth.set(len(self._queue))
        self._m_parked_depth.set(len(self._parked))
        self._m_preempt_depth.set(len(self._preempt_parked))
        if not self.idle():
            self._episode_open = True
            self._episode_t0 = now - float(
                manifest.get("episode_age_s", 0.0)
            )
        t_end = time.perf_counter()
        self._m_restore_snap_time.record(t_end - t_start)
        self.trace.span(
            "restore", t_start, t_end, track="engine",
            args={"path": path, "tick": self._tick_index},
        )

    def snapshot_auto(self, directory: str, keep_n: int = 3) -> str:
        """Write the next snapshot in a keep-N rotation under
        ``directory`` (``snap_NNNNNN``), pruning the oldest beyond
        ``keep_n``; orphaned ``.tmp_*`` dirs from a previously killed
        writer are garbage-collected first."""
        os.makedirs(directory, exist_ok=True)
        gc_orphan_tmpdirs(directory)
        self._snap_index += 1
        out = self.snapshot(
            os.path.join(directory, f"snap_{self._snap_index:06d}")
        )
        names = sorted(
            d for d in os.listdir(directory) if d.startswith("snap_")
        )
        for d in names[:-keep_n] if keep_n else []:
            shutil.rmtree(
                os.path.join(directory, d), ignore_errors=True
            )
        return out

    def restore_latest_snapshot(self, directory: str) -> Optional[str]:
        """Restore the newest snapshot under ``directory`` that passes
        integrity verification.  A corrupt snapshot (truncated npz,
        checksum mismatch) is skipped with a loud warning and the
        ``engine.faults.checkpoint_fallback`` counter, falling back to
        the previous one in the rotation.  Returns the restored path,
        or None when no usable snapshot exists."""
        if not os.path.isdir(directory):
            return None
        gc_orphan_tmpdirs(directory)
        names = sorted(
            (
                d for d in os.listdir(directory)
                if d.startswith("snap_")
                and os.path.exists(
                    os.path.join(directory, d, "manifest.json")
                )
            ),
            reverse=True,
        )
        for name in names:
            p = os.path.join(directory, name)
            try:
                self.restore(p)
                return p
            except CheckpointCorruptError as e:
                self._m_ckpt_fallback.inc()
                warnings.warn(
                    f"engine snapshot {p} failed integrity check "
                    f"({e}); falling back to the previous snapshot",
                    stacklevel=2,
                )
        return None

    # ----------------------------------------------------------- serving
    def idle(self) -> bool:
        """True when no request is queued, parked (admission plane or
        preemption buffer), resident in a slot, awaiting stats
        retirement, or finished-but-undelivered."""
        return (
            not self._queue
            and not self._parked
            and not self._preempt_parked
            and all(r is None for r in self._slot_req)
            and not self._inflight
            and not self._pending_results
        )

    def queue_depth(self) -> int:
        return len(self._queue)

    def parked_depth(self) -> int:
        return len(self._parked)

    def preempt_parked_depth(self) -> int:
        """Occupancy of the preemption parking buffer (displaced
        mid-window slots awaiting resume)."""
        return len(self._preempt_parked)

    def _fill_slot(self, s: int) -> None:
        """Admit into free slot ``s``: resume the most urgent
        preempt-parked window when it beats (or ties) the queue head —
        a started window wins ties, avoiding swap thrash — else pop the
        heap in priority/EDF order, shedding (or parking) candidates
        the feasibility check proves unmeetable, then fall back to the
        parked FIFO when the heap empties (best-effort service, marked
        ``parked`` on the result)."""
        while True:
            best = self._best_preempt_key()
            if best is not None and (
                not self._queue or best[0] <= self._queue[0][0][:3]
            ):
                self._resume_slot(s, self._preempt_parked.pop(best[1]))
                return
            if not self._queue:
                break
            _, rid, req, t_sub, dl = heapq.heappop(self._queue)
            verdict, reason = self._admission_verdict(req, dl)
            if verdict == shed_mod.ADMIT:
                self._admit(s, rid, req, t_sub, dl)
                return
            if verdict == shed_mod.PARK:
                self._park(rid, req, t_sub, dl, reason)
            else:
                self._shed(rid, req, t_sub, dl, reason)
        if self._parked:
            rid, req, t_sub, dl = self._parked.popleft()
            self._m_parked_depth.set(len(self._parked))
            self._admit(s, rid, req, t_sub, dl)
            self._slot_parked[s] = True

    def poll(self) -> List[StreamResult]:
        """One scheduler round: admit queued requests into free slots
        (priority/EDF order, feasibility-shedding if an admission policy
        is set), dispatch the next chunk, retire pipelined stats, and
        return the requests that finished — including shed and
        quarantined dispositions.  Non-blocking in the scheduling sense:
        returns [] when the engine is idle."""
        with self.trace.phase("engine.poll", track="engine"):
            self._maybe_preempt()
            for s in range(self.S):
                if self._slot_req[s] is None and (
                    self._queue or self._parked or self._preempt_parked
                ):
                    self._fill_slot(s)
            self._m_qdepth.set(len(self._queue))
            if (
                all(r is None for r in self._slot_req)
                and not self._inflight
            ):
                results, self._pending_results = self._pending_results, []
                if results and self.idle() and self._episode_open:
                    self._m_wall.set(time.perf_counter() - self._episode_t0)
                    self._episode_open = False
                if results:
                    self._sample()
                return results
            results = [self._finalize(s) for s in self._tick()]
            if self._pending_results:
                results = self._pending_results + results
                self._pending_results = []
            if self.idle() and self._episode_open:
                self._m_wall.set(time.perf_counter() - self._episode_t0)
                self._episode_open = False
            # one time-series point per tick, after completions land, so the
            # sample sees this tick's counters (misses included) — windowed
            # rates then track the run as it evolves
            self._sample()
            return results

    def drain(
        self, timeout_s: Optional[float] = None
    ) -> List[StreamResult]:
        """Poll until idle; returns results in completion order.

        ``timeout_s`` bounds the wall-clock wait: on expiry with the
        engine still not idle, raises :class:`EngineStallError` carrying
        a per-slot diagnostic snapshot (``stall_snapshot()``) and the
        results collected so far — a wedged tick loop used to spin here
        forever with no evidence of *which* slot stopped moving."""
        results: List[StreamResult] = []
        t0 = time.perf_counter()
        while not self.idle():
            results.extend(self.poll())
            if (
                timeout_s is not None
                and time.perf_counter() - t0 > timeout_s
                and not self.idle()
            ):
                snap = self.stall_snapshot()
                stuck = [
                    d["slot"] for d in snap["slots"]
                    if d["rid"] is not None
                ]
                raise EngineStallError(
                    f"drain() timed out after {timeout_s}s with the "
                    f"engine not idle: queue={snap['queue_depth']} "
                    f"parked={snap['parked_depth']} "
                    f"preempt_parked={snap['preempt_parked_depth']} "
                    f"inflight={snap['inflight']} "
                    f"stuck_slots={stuck}",
                    snap,
                    results,
                )
        return results

    def stall_snapshot(self) -> Dict:
        """Diagnostic view of everything that could be blocking
        progress: per-slot occupancy (request id, steps dispatched /
        retired / total, deadline), queue and parked depths *with*
        the parked request ids and the preemption parking-buffer
        occupancy (a drain timeout after heavy preemption is otherwise
        undiagnosable), in-flight stats chunks, and the tick index."""
        return {
            "tick": self._tick_index,
            "queue_depth": len(self._queue),
            "parked_depth": len(self._parked),
            "parked_rids": [rid for rid, _, _, _ in self._parked],
            "preempt_parked_depth": len(self._preempt_parked),
            "preempt_parked": [
                {
                    "rid": rec["rid"],
                    "priority": rec["priority"],
                    "done": rec["done"],
                    "total": rec["total"],
                    "deadline_s": rec["rel_deadline"],
                }
                for rec in self._preempt_parked
            ],
            "inflight": len(self._inflight),
            "pending_results": len(self._pending_results),
            "backend": self.backend,
            "slots": [
                {
                    "slot": s,
                    "rid": self._slot_req[s],
                    "done": int(self._slot_done[s]),
                    "retired": int(self._slot_retired[s]),
                    "total": int(self._slot_total[s]),
                    "deadline_s": self._slot_rel_deadline[s],
                    "parked": self._slot_parked[s],
                }
                for s in range(self.S)
            ],
        }

    def run(self, requests: List[StreamRequest]) -> List[StreamResult]:
        """Batch-compatibility wrapper over submit()/drain(): serve all
        requests and return results sorted by request id (submission
        order)."""
        for req in requests:
            self.submit(req)
        results = self.drain()
        results.sort(key=lambda r: r.request_id)
        return results

    # ------------------------------------------------------------- stats
    def events_per_sec(self) -> float:
        """Event throughput of the serving episode.

        Counters reset when an episode begins (first submit on an idle
        engine); the denominator is the *episode* clock — elapsed time
        since episode start while requests are in flight, the episode's
        final wall time once it drains — so mid-episode reads never mix a
        stale denominator with fresh numerators.  0.0 before any serving.
        """
        if self._episode_open:
            denom = time.perf_counter() - self._episode_t0
        else:
            denom = self.wall_s
        # quarantined requests' folded work is excluded: a poisoned
        # request that burned chunks before detection produced no
        # servable result, so counting its events would inflate
        # throughput exactly when the engine is misbehaving (shed
        # requests never reach a slot, so they never enter the numerator
        # in the first place)
        ev = self.total_events - self._m_q_events.value
        return max(ev, 0.0) / max(denom, 1e-9)

    def deadline_miss_rate(self) -> float:
        """Fraction of this episode's completed requests that missed
        their deadline (requests without a deadline count as met).

        Denominator policy: **ok completions only** (parked-then-served
        requests included).  Shed requests were refused service — they
        are neither misses nor completions, and surface in
        ``shed_rate()`` instead; quarantined requests failed for fault
        reasons, not scheduling reasons, and are excluded from both
        sides so a chaos run's miss rate remains comparable to a clean
        run's.
        """
        return self.deadline_misses / max(self.completed, 1)

    def shed_rate(self) -> float:
        """Lifetime fraction of submitted requests the admission plane
        shed (parked requests are not shed — they are served
        best-effort).  0.0 with no admission policy."""
        return self._m_shed.value / max(self._m_submitted.value, 1.0)

    def reset_tick_stats(self) -> None:
        """Zero the tick-phase instruments (e.g. after a warmup episode,
        so ``tick_breakdown`` reflects steady state, not first-tick
        compilation)."""
        self.metrics.reset(prefix="engine.tick.")

    def tick_breakdown(self) -> Dict[str, float]:
        """Engine-lifetime mean per-tick timing (derived from the
        ``engine.tick.*`` histograms' exact sums), the host-overhead
        evidence the serving benchmarks record next to raw chunk
        throughput.

        ``host_prep_us`` is pure host scheduling work.  ``dispatch_us``
        is the time spent in the chunk call: on backends with truly
        async dispatch (TPU) that is sub-millisecond enqueue cost and
        device compute surfaces in ``stats_fetch_us``; on backends that
        serialize dispatch behind the previous chunk's donated buffers
        (CPU here) it *includes* the device compute wait — read it as
        "tick minus host work", not as host dispatch overhead to
        attack.  ``stats_fetch_us`` is the blocking stats retirement
        (any remaining device wait + the single D2H fetch).  The
        ``kernel_*`` keys are the fused kernel's plan per call (zero on
        another backend)."""
        n = max(self._m_prep.count, 1)
        return {
            "ticks": self._m_prep.count,
            "pipeline_depth": self.pipeline_depth,
            "host_prep_us": self._m_prep.sum / n * 1e6,
            "dispatch_us": self._m_dispatch.sum / n * 1e6,
            "stats_fetch_us": self._m_fetch.sum / n * 1e6,
            "dispatch_p99_us": self._m_dispatch.percentile(99) * 1e6,
            "kernel_layer0_weight_bytes": self._m_k_weight.value,
            "kernel_event_row_bytes": self._m_k_events.value,
            "kernel_vmem_limit_bytes": self._m_k_vmem.value,
        }

    # -------------------------------------------------------- benchmarks
    def staged_chunk_args(self, trains: Sequence[np.ndarray]):
        """Stage ``trains`` (one per slot, (T, K) each) into fresh ring /
        meta / state pytrees and return ``(prepared, states, ring, meta)``
        — the argument tuple of ``chunk_for_timing()``.  Benchmark
        helper: measures the resident chunk exactly as the tick loop runs
        it, without mutating the live engine."""
        if len(trains) != self.S:
            raise ValueError(f"need {self.S} trains, got {len(trains)}")
        states = self._on_slots(runtime.init_states(self.cfg, self.S))
        ring = self._alloc_ring(
            max(self._ring_steps, max(t.shape[0] for t in trains))
        )
        meta = self._on_slots({
            "done": jnp.zeros((self.S,), jnp.int32),
            "total": jnp.asarray(
                [t.shape[0] for t in trains], jnp.int32
            ),
            "admit": jnp.zeros((self.S,), jnp.int32),
            "fault": jnp.zeros((self.S,), jnp.int32),
        })
        for s, t in enumerate(trains):
            train = jax.device_put(np.asarray(t, np.float32))
            # same slot dtype as _admit(): a bare python int would hit a
            # separate (weak-typed) jit cache entry and recompile
            slot = jax.device_put(np.int32(s))
            ring, meta = self._admit_spikes_fn(ring, meta, train, slot)
        meta = {**meta, "admit": self._on_slots(jnp.zeros((self.S,), jnp.int32))}
        return self._prepared, states, ring, meta

    def chunk_for_timing(self):
        """The compiled chunk *without* buffer donation, safe to invoke
        repeatedly on the same arguments (``time_fn``-style benchmarks);
        the tick loop itself uses the donating twin."""
        return self._chunk_nodonate
