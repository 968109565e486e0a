"""A cell cut to a size the CPU runs in a second, for tests only: the
64 px cell's configuration and mix with a 64-32-2 network over 8x8
frames."""

import dataclasses

from perfbench import harness, spec


def tiny_cell(name: str = "c64-frames-max", **traffic):
    cell = spec.cell(spec.load(harness.ROOT), harness.ROOT, name)
    cfg = {**cell.config, "layer_sizes": [64, 32, 2]}
    mix = {**cell.traffic, "image_hw": 8, **traffic}
    return dataclasses.replace(cell, config=cfg, traffic=mix)


def run_tiny(cell, seed=5, seconds=0.6, **kw):
    import time

    import jax

    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            devices=jax.devices()[:1], log=lambda *a: None,
                            **kw)
