"""Static VMEM/SMEM budget estimation for the repo's Pallas kernels.

Rather than re-deriving BlockSpecs by hand (which would rot the moment a
kernel changes), this module *captures* the real ``pl.pallas_call``
arguments: it temporarily replaces ``pallas_call`` with a recording
stub, invokes each kernel's unjitted wrapper (``fn.__wrapped__``) at a
representative geometry, and analyses exactly the grid / BlockSpecs /
scratch the wrapper would hand to Mosaic.

Per kernel it reports:

- estimated VMEM working set: one copy of every *resident* block (index
  map constant over the grid — e.g. the scalar-prefetched weight slabs
  in ``snn_chunk``), two copies of every *pipelined* block (Pallas
  double-buffers blocks whose index map varies), plus scratch;
- estimated SMEM bytes (the scalar-prefetch operands, plus the copies
  of every block whose BlockSpec asks for SMEM);
- an index-map bounds check: every index map is evaluated at every grid
  corner and the produced block must lie inside the (padded) operand;
- a divisibility check: padded operand dims must be multiples of the
  block dims (the Mosaic blocked-indexing contract).

Findings use codes RB301 (VMEM over budget), RB302 (index map out of
bounds), RB303 (block does not divide operand), RB304 (SMEM over
budget).  A kernel's VMEM budget is the scoped limit it asks Mosaic for
(``vmem_limit_bytes``), or the compiler's default of 16 MiB where it asks
none, and no limit may pass the core's VMEM (128 MiB on a v5e); SMEM has
a deliberately tight 1 MiB line.  The estimate covers *declared*
buffers only — compiler-managed temporaries (e.g. the (bm, bk, bn)
int32 product in ``q115_matmul``) are the compiler's to spill.

``snn_chunk`` does not only get checked here: it stages itself by
``snn_chunk_staging``, which prices its geometry from the shapes and
derives how many steps of event rows a grid step brings into SMEM and
the scoped VMEM limit; the captured call then checks that price.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Sequence

import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .jaxlint import Finding

DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024  # scoped VMEM of a kernel asking none
DEFAULT_SMEM_BUDGET = 1024 * 1024  # scalar-prefetch tables + SMEM blocks
VMEM_CAPACITY = 128 * 1024 * 1024  # a TPU v5e core's VMEM: the most to ask
# beside the declared blocks: their padding to (8, 128) tiles (about 0.5
# MiB at 16384-512-2) and Mosaic's own scratch
_VMEM_HEADROOM = 4 * 1024 * 1024
_LANE = 128  # lane width: the kernels pad widths to it
_EV_PAD = 128  # snn_chunk's event-count lane

# the (4096, 512, 2) collision config — the paper's headline workload
# and what stream_bench drives; snn_chunk is priced at the serving
# engine's defaults (8 slots, Tc=5, no capacity plan: C = fan-in) for it
# and for the paper's 128 px input, 16384-512-2
_COLLISION_LAYERS = ((4096, 512), (512, 2))
_COLLISION_128PX = (16384, 512, 2)
_SLOTS = 8
_CHUNK_STEPS = 5
_CAPACITY = 13 * 128  # layer-0 event capacity (autotuned ballpark)


@dataclasses.dataclass(frozen=True)
class ChunkStaging:
    """How ``snn_chunk`` stages one call, priced from its shapes."""

    steps_per_block: int  # steps of one slot's event rows per grid step
    weight_bytes: int  # layer-0 slab, brought into VMEM once a call
    event_row_bytes: int  # address and value rows staged into SMEM a call
    smem_bytes: int
    vmem_bytes: int  # declared VMEM working set
    vmem_limit_bytes: int  # the scoped VMEM limit the kernel is given


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def snn_chunk_staging(
    fan_ins: Sequence[int],
    outs: Sequence[int],
    slots: int,
    chunk_steps: int,
    capacity: int,
    block_e: int = 128,
) -> ChunkStaging:
    """Price one ``snn_chunk`` call at the geometry it is given (unpadded
    fan-ins and widths, slots, chunk steps, layer-0 event capacity) and
    choose its staging; the kernel stages itself by this.

    Event rows come in ``ts`` steps of one slot per grid step, ``ts`` the
    largest divisor of the chunk whose double-buffered address and value
    blocks, with the prefetched mask and counts, fit SMEM.  VMEM is
    counted as ``check_kernel_budgets`` counts a captured call: blocks
    constant over the grid (the slabs) once, per-slot blocks twice.  The
    scoped limit is that working set plus headroom for tile padding and
    Mosaic's own scratch, and never below the compiler's default.  A
    geometry that cannot fit is priced all the same;
    ``check_kernel_budgets`` reports it.
    """
    cp = _round_up(capacity, min(block_e, capacity))
    np_out = [_round_up(n, _LANE) for n in outs]
    rows = [int(fan_ins[0])] + np_out[:-1]  # hidden slabs take padded rows
    prefetch = 4 * (slots + slots * chunk_steps)  # active mask, counts

    def smem(ts: int) -> int:
        return prefetch + 2 * 2 * ts * cp * 4  # two tables, two copies

    ts = max((d for d in range(1, chunk_steps + 1)
              if chunk_steps % d == 0 and smem(d) <= DEFAULT_SMEM_BUDGET),
             default=1)
    f32 = 4
    row = sum(np_out) * f32  # one (1, N) row of every layer
    vmem = (
        sum(r * n for r, n in zip(rows, np_out)) * f32  # slabs, resident
        + 3 * row  # biases, betas, thresholds, resident
        + 2 * 2 * row  # incoming membranes and refractory, per slot
        + 2 * 2 * row  # final membranes and refractory, per slot
        + 2 * (2 * np_out[-1] + _EV_PAD) * chunk_steps * f32  # mem, spk, ev
        + 2 * row  # state scratch
    )
    limit = max(DEFAULT_VMEM_BUDGET,
                _round_up(vmem + _VMEM_HEADROOM, 1 << 20))
    return ChunkStaging(
        steps_per_block=ts,
        weight_bytes=rows[0] * np_out[0] * f32,
        event_row_bytes=slots * chunk_steps * cp * 2 * 4,
        smem_bytes=smem(ts),
        vmem_bytes=vmem,
        vmem_limit_bytes=limit,
    )


@dataclasses.dataclass
class BufferPlan:
    name: str
    role: str  # "in" | "out" | "scratch" | "prefetch"
    block_shape: tuple[int, ...]
    dtype: str
    bytes_per_copy: int
    copies: int  # 1 resident, 2 pipelined
    resident: bool

    @property
    def bytes(self) -> int:
        return self.bytes_per_copy * self.copies

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["bytes"] = self.bytes
        return d


@dataclasses.dataclass
class KernelPlan:
    kernel: str
    grid: tuple[int, ...]
    num_scalar_prefetch: int
    buffers: list[BufferPlan]
    smem_bytes: int
    errors: list[str]
    vmem_limit_bytes: int | None = None  # the scoped limit it asks for

    @property
    def vmem_bytes(self) -> int:
        return sum(b.bytes for b in self.buffers)

    def to_json(self) -> dict:
        return {
            "kernel": self.kernel,
            "grid": list(self.grid),
            "num_scalar_prefetch": self.num_scalar_prefetch,
            "vmem_bytes": self.vmem_bytes,
            "smem_bytes": self.smem_bytes,
            "vmem_limit_bytes": self.vmem_limit_bytes,
            "buffers": [b.to_json() for b in self.buffers],
            "errors": self.errors,
        }


# ---------------------------------------------------------------------------
# pallas_call capture
# ---------------------------------------------------------------------------


class _Capture:
    """Swap ``pallas_call`` for a recorder that returns zeros."""

    def __init__(self) -> None:
        self.records: list[dict] = []
        self._real: Any = None

    def __enter__(self) -> "_Capture":
        self._real = pl.pallas_call

        records = self.records

        def fake_pallas_call(kernel, **kw):
            def runner(*operands):
                records.append({"kw": kw, "operands": operands})
                out_shape = kw.get("out_shape")
                if isinstance(out_shape, (list, tuple)):
                    return [jnp.zeros(s.shape, s.dtype) for s in out_shape]
                return jnp.zeros(out_shape.shape, out_shape.dtype)

            return runner

        pl.pallas_call = fake_pallas_call
        return self

    def __exit__(self, *exc: Any) -> None:
        pl.pallas_call = self._real


def _itemsize(dtype: Any) -> int:
    return int(np.dtype(jnp.dtype(dtype)).itemsize)


def _as_list(x: Any) -> list:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _grid_corners(grid: Sequence[int]) -> list[tuple[int, ...]]:
    axes = [sorted({0, max(0, g - 1)}) for g in grid]
    return [tuple(c) for c in itertools.product(*axes)]


def _eval_index_map(
    spec: Any, corners: Sequence[tuple[int, ...]], num_prefetch: int
) -> tuple[list[tuple[int, ...]] | None, str | None]:
    """Evaluate a BlockSpec's index map at the grid corners.

    Prefetch refs are passed as ``None`` placeholders (the repo's index
    maps never dereference them).  Returns (indices, error).
    """
    imap = getattr(spec, "index_map", None)
    if imap is None:
        return None, None
    out = []
    for c in corners:
        try:
            idx = imap(*c, *([None] * num_prefetch))
        except TypeError:
            try:
                idx = imap(*c)
            except Exception as e:
                return None, f"index map raised {type(e).__name__}: {e}"
        except Exception as e:
            return None, f"index map raised {type(e).__name__}: {e}"
        if not isinstance(idx, tuple):
            idx = (idx,)
        out.append(tuple(int(i) for i in idx))
    return out, None


def _analyze_record(name: str, rec: dict) -> KernelPlan:
    kw = rec["kw"]
    operands = rec["operands"]
    grid_spec = kw.get("grid_spec")
    if grid_spec is not None:
        grid = tuple(grid_spec.grid)
        in_specs = _as_list(grid_spec.in_specs)
        out_specs = _as_list(grid_spec.out_specs)
        scratch = _as_list(grid_spec.scratch_shapes)
        npf = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
    else:
        grid = tuple(kw.get("grid") or ())
        in_specs = _as_list(kw.get("in_specs"))
        out_specs = _as_list(kw.get("out_specs"))
        scratch = _as_list(kw.get("scratch_shapes"))
        npf = 0
    out_shapes = _as_list(kw.get("out_shape"))
    corners = _grid_corners(grid)

    buffers: list[BufferPlan] = []
    errors: list[str] = []
    smem = 0
    smem_blocks: list[int] = []

    # scalar-prefetch operands live whole in SMEM
    for i in range(npf):
        op = operands[i]
        smem += int(np.prod(op.shape)) * _itemsize(op.dtype) if op.shape else _itemsize(op.dtype)

    def add(spec, operand_shape, dtype, role, label):
        nonlocal errors
        # a squeezed dim (None) is a block of one element
        bshape = tuple(
            1 if b is None else int(b) for b in (spec.block_shape or ())
        )
        if not bshape:
            bshape = tuple(int(s) for s in operand_shape)
        per_copy = int(np.prod(bshape)) * _itemsize(dtype)
        idxs, err = _eval_index_map(spec, corners, npf)
        resident = False
        if err:
            errors.append(f"{label}: {err}")
        elif idxs is not None:
            resident = len(set(idxs)) == 1
            for c, idx in zip(corners, idxs):
                if len(idx) != len(bshape):
                    errors.append(
                        f"{label}: index map rank {len(idx)} != block rank {len(bshape)}"
                    )
                    break
                for d, (bi, bs, os) in enumerate(zip(idx, bshape, operand_shape)):
                    if bi < 0 or (bi + 1) * bs > os:
                        errors.append(
                            f"{label}: grid point {c} maps block {idx} outside "
                            f"operand dim {d} (block {bs} x idx {bi} vs size {os})"
                        )
            for d, (bs, os) in enumerate(zip(bshape, operand_shape)):
                if bs and os % bs:
                    errors.append(
                        f"{label}: block dim {d} ({bs}) does not divide "
                        f"operand dim ({os})"
                    )
        if "smem" in str(getattr(spec, "memory_space", "")).lower():
            # a pipelined SMEM block: its copies count against SMEM
            smem_blocks.append(per_copy * (1 if resident else 2))
            return
        buffers.append(
            BufferPlan(
                name=label,
                role=role,
                block_shape=bshape,
                dtype=np.dtype(jnp.dtype(dtype)).name,
                bytes_per_copy=per_copy,
                copies=1 if resident else 2,
                resident=resident,
            )
        )

    data_ops = operands[npf:]
    for i, spec in enumerate(in_specs):
        if i < len(data_ops):
            op = data_ops[i]
            add(spec, tuple(op.shape), op.dtype, "in", f"in[{i}]")
        else:
            errors.append(f"in[{i}]: no matching operand captured")
    for i, (spec, s) in enumerate(zip(out_specs, out_shapes)):
        add(spec, tuple(s.shape), s.dtype, "out", f"out[{i}]")
    for i, sc in enumerate(scratch):
        shape = tuple(int(x) for x in getattr(sc, "shape", ()) or ())
        dtype = getattr(sc, "dtype", jnp.float32)
        nbytes = int(np.prod(shape)) * _itemsize(dtype) if shape else _itemsize(dtype)
        space = str(getattr(sc, "memory_space", "vmem")).lower()
        if "smem" in space:
            smem += nbytes
        else:
            buffers.append(
                BufferPlan(f"scratch[{i}]", "scratch", shape,
                           np.dtype(jnp.dtype(dtype)).name, nbytes, 1, True)
            )

    limit = getattr(kw.get("compiler_params"), "vmem_limit_bytes", None)
    return KernelPlan(name, grid, npf, buffers, smem + sum(smem_blocks),
                      errors, limit)


# ---------------------------------------------------------------------------
# per-kernel drivers (representative geometry: the collision config)
# ---------------------------------------------------------------------------


def plan_snn_chunk(
    layer_sizes: Sequence[int] = (4096, 512, 2),
    slots: int = _SLOTS,
    chunk_steps: int = _CHUNK_STEPS,
    capacity: int | None = None,
) -> KernelPlan:
    """Capture ``snn_chunk`` at a geometry (``capacity`` None: the fan-in,
    as the engine stages without a capacity plan) on abstract shapes, so
    a slab of any size costs no memory."""
    import jax

    from repro.kernels import snn_chunk as mod

    B, Tc = slots, chunk_steps
    C = layer_sizes[0] if capacity is None else capacity
    n = layer_sizes[1:]

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    args = (
        [f32(k, m) for k, m in zip(layer_sizes[:-1], n)],
        [f32(m) for m in n], [f32(m) for m in n], [f32(m) for m in n],
        [f32(B, m) for m in n],
        [jax.ShapeDtypeStruct((B, m), jnp.int32) for m in n],
        jax.ShapeDtypeStruct((B, Tc, C), jnp.int16),
        jax.ShapeDtypeStruct((B, Tc, C), jnp.int8),
        jax.ShapeDtypeStruct((B, Tc), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
    )
    with _Capture() as cap:
        jax.eval_shape(
            lambda *a: mod.snn_chunk.__wrapped__(
                *a, interpret=True, layout="slot_major"),
            *args,
        )
    return _analyze_record("snn_chunk", cap.records[-1])


def _plan_aer_matmul() -> KernelPlan:
    from repro.kernels import aer_matmul as mod

    K, N, E = _COLLISION_LAYERS[0][0], _COLLISION_LAYERS[0][1], _CAPACITY
    addrs = np.zeros((E,), np.int32)
    values = np.zeros((E,), np.int32)
    weights_q = np.zeros((K, N), np.int16)
    with _Capture() as cap:
        mod.aer_spike_matmul.__wrapped__(addrs, values, weights_q, interpret=True)
    return _analyze_record("aer_spike_matmul", cap.records[-1])


def _plan_aer_matmul_batched() -> KernelPlan:
    from repro.kernels import aer_matmul as mod

    K, N, E, B = _COLLISION_LAYERS[0][0], _COLLISION_LAYERS[0][1], _CAPACITY, 8
    addrs = np.zeros((B, E), np.int32)
    values = np.zeros((B, E), np.int32)
    weights_q = np.zeros((K, N), np.int16)
    with _Capture() as cap:
        mod.aer_spike_matmul_batched.__wrapped__(addrs, values, weights_q, interpret=True)
    return _analyze_record("aer_spike_matmul_batched", cap.records[-1])


def _plan_lif_fused() -> KernelPlan:
    from repro.kernels import lif_fused as mod

    T, B, N = 25, 8, _COLLISION_LAYERS[0][1]
    currents = np.zeros((T, B, N), np.float32)
    beta = np.full((N,), 0.9, np.float32)
    threshold = np.ones((N,), np.float32)
    with _Capture() as cap:
        mod.lif_fused.__wrapped__(currents, beta, threshold, interpret=True)
    return _analyze_record("lif_fused", cap.records[-1])


def _plan_q115_matmul() -> KernelPlan:
    from repro.kernels import q115_matmul as mod

    M, K, N = 8, _COLLISION_LAYERS[0][0], _COLLISION_LAYERS[0][1]
    x_q = np.zeros((M, K), np.int16)
    w_q = np.zeros((K, N), np.int16)
    with _Capture() as cap:
        mod.q115_matmul.__wrapped__(x_q, w_q, interpret=True)
    return _analyze_record("q115_matmul", cap.records[-1])


KERNEL_PLANNERS: dict[str, Callable[[], KernelPlan]] = {
    "snn_chunk": plan_snn_chunk,
    "snn_chunk_128px": lambda: dataclasses.replace(
        plan_snn_chunk(_COLLISION_128PX), kernel="snn_chunk_128px"),
    "aer_spike_matmul": _plan_aer_matmul,
    "aer_spike_matmul_batched": _plan_aer_matmul_batched,
    "lif_fused": _plan_lif_fused,
    "q115_matmul": _plan_q115_matmul,
}

_KERNEL_PATHS = {
    "snn_chunk": "src/repro/kernels/snn_chunk.py",
    "snn_chunk_128px": "src/repro/kernels/snn_chunk.py",
    "aer_spike_matmul": "src/repro/kernels/aer_matmul.py",
    "aer_spike_matmul_batched": "src/repro/kernels/aer_matmul.py",
    "lif_fused": "src/repro/kernels/lif_fused.py",
    "q115_matmul": "src/repro/kernels/q115_matmul.py",
}


def plan_findings(
    plan: KernelPlan,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    smem_budget: int = DEFAULT_SMEM_BUDGET,
    vmem_capacity: int = VMEM_CAPACITY,
    path: str | None = None,
) -> list[Finding]:
    """What a plan breaks: a working set over the scoped limit the kernel
    asks for (``vmem_budget`` where it asks none), a limit over the
    core's VMEM, SMEM over its line, and the index-map errors."""
    name = plan.kernel
    path = path or _KERNEL_PATHS.get(name, f"<kernel:{name}>")
    findings: list[Finding] = []
    budget = plan.vmem_limit_bytes or vmem_budget
    if plan.vmem_bytes > budget or budget > vmem_capacity:
        findings.append(
            Finding(
                path, 1, 0, "RB301",
                f"{name}: estimated VMEM working set "
                f"{plan.vmem_bytes / 2**20:.2f} MiB, scoped limit "
                f"{budget / 2**20:.2f} MiB, core VMEM "
                f"{vmem_capacity / 2**20:.2f} MiB",
            )
        )
    if plan.smem_bytes > smem_budget:
        findings.append(
            Finding(
                path, 1, 0, "RB304",
                f"{name}: SMEM {plan.smem_bytes / 2**10:.0f} KiB "
                f"exceeds budget {smem_budget / 2**10:.0f} KiB",
            )
        )
    for err in plan.errors:
        code = "RB303" if "does not divide" in err else "RB302"
        findings.append(Finding(path, 1, 0, code, f"{name}: {err}"))
    return findings


def check_kernel_budgets(
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    smem_budget: int = DEFAULT_SMEM_BUDGET,
    kernels: Sequence[str] | None = None,
    vmem_capacity: int = VMEM_CAPACITY,
) -> tuple[list[KernelPlan], list[Finding]]:
    """Capture + analyse every kernel; returns (plans, findings)."""
    plans: list[KernelPlan] = []
    findings: list[Finding] = []
    for name in kernels or KERNEL_PLANNERS:
        path = _KERNEL_PATHS.get(name, f"<kernel:{name}>")
        try:
            plan = KERNEL_PLANNERS[name]()
        except Exception as e:
            findings.append(
                Finding(path, 1, 0, "RB302", f"{name}: capture failed: {type(e).__name__}: {e}")
            )
            continue
        plans.append(plan)
        findings += plan_findings(plan, vmem_budget, smem_budget,
                                  vmem_capacity, path)
    return plans, findings
