"""Logical-axis partitioning rules (MaxText-style) -> PartitionSpecs.

Every param/activation dim carries a logical name; rules map names to mesh
axes.  `spec_for` walks a shape's logical axes in order, assigning mesh
axes when (a) the rule's axes exist in the mesh, (b) the dim is divisible
by their total size, and (c) no axis is used twice in one spec — so the
same rule table serves 1-device smoke tests, the 256-chip pod and the
512-chip multi-pod mesh, degrading gracefully (e.g. yi-34b's 56 heads are
not 16-divisible -> heads fall back to replicated; the roofline analysis
§Perf quantifies that cost and the hillclimb fixes it).

Parallelism profiles (see DESIGN.md §4):
  pod   : pure data parallel (cross-pod traffic = one grad all-reduce)
  data  : FSDP (embed-dim sharding of params/optimizer) + batch DP
  model : tensor parallel (heads / mlp / experts / vocab)
"""

from __future__ import annotations

import dataclasses
import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# logical dim name -> mesh axes (applied together, in order)
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "embed": ("data",),  # FSDP shard of params + optimizer
    "vocab": ("model",),
    "heads": ("model",),
    "kv": ("model",),
    "mlp": ("model",),
    "expert": ("model",),
    "inner": ("model",),
    "lru": ("model",),
    "lru_in": (),
    "state": ("model",),
    "q_rank": (),
    "kv_rank": (),
    "clip": (),
    "codebook": (),
    "groups": (),
    "layers": (),
    "seq": ("model",),  # decode-cache seq dim: context parallel over model
    "head_dim": (),
    "conv_w": (),
    # activation-only logical dims
    "act_seq": (),  # set to ("data",) for sequence-parallel profiles
    "embed_act": (),  # activation feature dim stays replicated
    "cap": (),  # MoE expert-capacity dim
    # streaming-SNN serving dims (serving/snn_engine device-resident state)
    "slot": ("pod", "data"),  # engine micro-batch slot axis (like batch)
    "ring_steps": (),  # per-slot event-ring time axis: stays with its slot
    "event_cap": (),  # packed per-step event-list capacity: replicated
}


@dataclasses.dataclass(frozen=True)
class PartitionRules:
    table: Dict[str, Tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )

    def override(self, **kw) -> "PartitionRules":
        t = dict(self.table)
        for k, v in kw.items():
            t[k] = tuple(v) if v else ()
        return PartitionRules(t)


def spec_for(
    shape: Sequence[int],
    axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[PartitionRules] = None,
) -> P:
    """Build a PartitionSpec for one array."""
    rules = rules or PartitionRules()
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used = set()
    parts = []
    for dim, name in zip(shape, axes):
        assigned: Tuple[str, ...] = ()
        if name is not None:
            cand = tuple(
                ax
                for ax in rules.table.get(name, ())
                if ax in mesh_sizes and ax not in used
            )
            if cand:
                total = int(np.prod([mesh_sizes[ax] for ax in cand]))
                if dim % total == 0:
                    assigned = cand
                else:
                    # try progressively shorter prefixes (e.g. just "pod")
                    for k in range(len(cand) - 1, 0, -1):
                        total = int(np.prod([mesh_sizes[ax] for ax in cand[:k]]))
                        if dim % total == 0:
                            assigned = cand[:k]
                            break
        used.update(assigned)
        if len(assigned) == 0:
            parts.append(None)
        elif len(assigned) == 1:
            parts.append(assigned[0])
        else:
            parts.append(assigned)
    # trim trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_specs(
    shapes: PyTree, axes: PyTree, mesh: Mesh,
    rules: Optional[PartitionRules] = None,
) -> PyTree:
    """Map spec_for over matching (shapes, logical-axes) pytrees."""

    def one(s, a):
        return spec_for(s.shape, a, mesh, rules)

    return jax.tree_util.tree_map(
        one, shapes, axes,
        is_leaf=lambda t: isinstance(t, tuple)
        and all(isinstance(x, (str, type(None))) for x in t),
    )


def tree_shardings(shapes, axes, mesh, rules=None) -> PyTree:
    specs = tree_specs(shapes, axes, mesh, rules)
    return jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda t: isinstance(t, P),
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def slot_axis(num_slots: int, mesh: Mesh,
              rules: Optional[PartitionRules] = None):
    """Mesh axes the serving engine's slot dimension shards over.

    Everything slot-indexed in the stream engine — neuron states, the
    per-slot event ring buffers ((S, ring_steps, event_cap), via the
    ``slot``/``ring_steps``/``event_cap`` rules), scheduling metadata and
    the per-chunk stats — shards along this one axis; a ``P(slot_axis)``
    pytree *prefix* therefore covers all of them.  Raises loudly when
    ``num_slots`` does not divide the mesh's slot axes: a silently
    replicated slot axis would run every slot on every device, which is
    exactly the misconfiguration sharded serving exists to avoid.
    """
    spec = spec_for((num_slots,), ("slot",), mesh, rules)
    if len(spec) == 0 or spec[0] is None:
        raise ValueError(
            f"num_slots={num_slots} is not shardable over mesh axes "
            f"{dict(zip(mesh.axis_names, mesh.devices.shape))}; pick a "
            f"slot count divisible by the mesh's batch axes"
        )
    return spec[0]


# ------------------------------------------------- activation constraints
# MaxText-style: model code calls `constrain(x, logical_axes)` at the key
# activation points (block inputs, attention heads, mlp hidden, MoE
# buffers, logits).  Outside an `activation_sharding` context (smoke
# tests, 1-device runs) it is a no-op; inside (dry-run / production
# launch) it pins the intermediate sharding so XLA's propagation cannot
# pick pathological layouts (measured: granite train_4k dropped from
# 831 GB temp / 12.3 s collective to per-device-sane values; see
# EXPERIMENTS.md §Perf notes).

_act_ctx = threading.local()


@contextmanager
def activation_sharding(mesh: Mesh, rules: Optional[PartitionRules] = None):
    prev = getattr(_act_ctx, "val", None)
    _act_ctx.val = (mesh, rules or PartitionRules())
    try:
        yield
    finally:
        _act_ctx.val = prev


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    ctx = getattr(_act_ctx, "val", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = spec_for(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ----------------------------------------------------------- cache axes
_CACHE_LEAF_AXES: Dict[str, Tuple[str, ...]] = {
    "k": ("batch", "seq", "kv", "head_dim"),
    "v": ("batch", "seq", "kv", "head_dim"),
    "k_scale": ("batch", "seq", "kv"),
    "v_scale": ("batch", "seq", "kv"),
    "c_kv": ("batch", "seq", "kv_rank"),
    "k_rope": ("batch", "seq", "head_dim"),
    "state": ("batch", "heads", "head_dim", "state"),
    "conv_x": ("batch", "conv_w", "inner"),
    "conv_B": ("batch", "conv_w", "state"),
    "conv_C": ("batch", "conv_w", "state"),
    "h": ("batch", "lru"),
    "conv": ("batch", "conv_w", "lru"),
}


def cache_logical_axes(cache_shapes: PyTree) -> PyTree:
    """Derive logical axes for a decode-cache pytree from leaf names.

    Stacked layer dims (from scan groups) are detected by ndim mismatch
    and get a leading 'layers' axis.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    out = []
    for path, leaf in flat:
        name = None
        for k in reversed(path):
            if isinstance(k, jax.tree_util.DictKey):
                name = k.key
                break
        base = _CACHE_LEAF_AXES[name]
        extra = leaf.ndim - len(base)
        axes = ("layers",) * extra + base
        out.append(axes)
    return jax.tree_util.tree_unflatten(treedef, out)


# ----------------------------------------------------------- optimizer
def opt_state_specs(opt_state, param_specs, mesh) -> PyTree:
    """Optimizer states shard like their params (mu/nu mirror params);
    scalar counts are replicated."""

    def one(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim == 0:
            return NamedSharding(mesh, P())
        return None  # placeholder, replaced below via structure match

    # AdamState/SGDState are NamedTuples of (count?, tree, tree)
    import jax.tree_util as jtu

    def map_state(state):
        if isinstance(state, tuple) and hasattr(state, "_fields"):
            return type(state)(*[map_state(s) for s in state])
        # a pytree shaped like params
        treedef_p = jtu.tree_structure(param_specs)
        treedef_s = jtu.tree_structure(state)
        if treedef_p == treedef_s:
            return param_specs
        if hasattr(state, "ndim"):
            return NamedSharding(mesh, P())
        return jtu.tree_map(lambda _: NamedSharding(mesh, P()), state)

    return map_state(opt_state)
