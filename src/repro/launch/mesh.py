"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — smoke tests see 1 CPU device;
only launch/dryrun.py forces 512 placeholder devices via XLA_FLAGS.

Production topology (TPU v5e target):
  single pod : (16, 16)    axes (data, model)   = 256 chips
  multi pod  : (2, 16, 16) axes (pod, data, model) = 512 chips
    pod   — pure data parallelism (one cross-pod grad all-reduce / step,
            DCN-friendly; gradient compression hooks apply here)
    data  — FSDP + batch DP (intra-pod ICI)
    model — tensor parallel (heads/mlp/experts/vocab)
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(
    *,
    multi_pod: bool = False,
    shape: Optional[Tuple[int, ...]] = None,
    axes: Optional[Tuple[str, ...]] = None,
):
    """Build the production mesh.  `shape`/`axes` overrides exist for the
    §Perf hillclimb (e.g. (32, 8) data/model remapping for yi-34b) and for
    small-device tests; the defaults are the assignment's meshes."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    assert axes is not None and len(axes) == len(shape)
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_host_mesh(model: int = 1):
    """Whatever-fits mesh for single-host smoke runs: (n_dev/model, model)."""
    n = len(jax.devices())
    data = max(n // model, 1)
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
