"""Bring-up check: serve the paper's 4096-512-2 SNN on a TPU through the
fused chunk kernel and compare every result with a plain reference.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chips # slot-sharded engine over 4 chips

One chip: builds ``SNNStreamEngine`` with its defaults (backend "auto",
8 slots, 5-step chunks, full fan-in event capacity C=4096) for
``configs/collision_snn.py:CONFIG``, serves 16 rate-coded collision
scenes whose spike trains come from a fixed numpy seed, checks that the
chunk ran through the Mosaic-compiled kernel with no fallback, retry,
quarantine or steady-state recompile, and compares every result with
``core.snn.forward`` (dense float32 matmuls at "highest" precision) on the
same chip.  Then it runs ``launch/serve.py``'s ``main`` once at the same
width.

``--four-chips`` runs only the slot-sharded engine over a 4-chip mesh
against the one-chip engine on the same requests; results must be equal.

Each phase prints what it saw; the last line of standard output is one
JSON object ``{"ok": true, "device": {...}}``.  Without a TPU, or when a
phase fails, the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import warnings

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
NUM_REQUESTS = 16
# one seed for the random weights and the numpy spike trains
SEED = 0
# Gain on the output layer's random weights and bias.  At the init scale
# the two output neurons hardly fire (one spike in 16 windows), so output
# spike counts would test nothing and every prediction would come from
# the membrane tie-break; at this gain both fire several times a window.
OUT_GAIN = 8.0
# How far spike counts may differ from the reference over a 25-step
# window: OUT_TOL per output neuron, HIDDEN_TOL for a request's whole
# hidden layer (~1000 spikes from 512 neurons).  Both sides compute in
# float32 at full precision, but in different orders: the kernel adds
# layer-0 weight rows one event at a time, the reference does a dense
# matmul.  The sums then differ in the last bits, which flips a threshold
# compare only where a membrane sits within rounding distance of it; at
# these rates that is expected less than once per run.  One flip moves a
# count by one, and a zero-reset LIF neuron is back in step within a few
# steps, so a flip costs at most a few counts in all.
OUT_TOL = 2.0
HIDDEN_TOL = 4.0


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def collision_trains(num: int, hw: int, steps: int, seed: int):
    """Rate-coded collision scenes as (steps, hw*hw) {0,1} spike trains,
    drawn on the host from a fixed numpy seed."""
    from repro.data import collision

    _, _, images, _ = collision.generate(
        collision.CollisionConfig(
            image_hw=hw, num_train=0, num_test=num, seed=seed
        )
    )
    rng = np.random.default_rng(seed)
    flat = images.reshape(num, -1)
    return [
        (rng.random((steps, flat.shape[1])) < img[None, :]).astype(np.float32)
        for img in flat
    ]


def smoke_params(cfg):
    """Random weights from ``SEED``, the output layer scaled by
    ``OUT_GAIN``."""
    import jax

    from repro.core import snn

    params = snn.init_params(jax.random.PRNGKey(SEED), cfg)
    last = f"layer{len(cfg.layer_sizes) - 2}"
    out = {**params[last]}
    out["w"] = out["w"] * OUT_GAIN
    out["b"] = out["b"] * OUT_GAIN
    return {**params, last: out}


def reference(params, cfg, trains):
    """``core.snn.forward`` on the same chip, float32 matmuls at "highest"
    precision: predictions, output spike counts (B, n_class) and hidden
    spike totals (B,).  The hidden layer's spikes come from the same
    forward run on the first layer alone."""
    import jax

    from repro.core import snn

    spikes = jax.device_put(np.stack(trains, axis=1))  # (T, B, K)
    hidden_cfg = dataclasses.replace(cfg, layer_sizes=cfg.layer_sizes[:2])
    with jax.default_matmul_precision("highest"):
        out_mem, out_spk = jax.jit(
            lambda p, s: snn.forward(p, s, cfg)
        )(params, spikes)
        _, hid_spk = jax.jit(
            lambda p, s: snn.forward(p, s, hidden_cfg)
        )({"layer0": params["layer0"]}, spikes)
        pred = snn.predict_from_traces(out_mem, out_spk)
    return (
        np.asarray(pred),
        np.asarray(out_spk).sum(axis=0),
        np.asarray(hid_spk).sum(axis=(0, 2)),
    )


def counter(engine, name: str) -> float:
    return float(engine.metrics_snapshot()[name]["value"])


def serve(engine, trains):
    from repro.serving.snn_engine import StreamRequest

    t0 = time.perf_counter()
    results = engine.run([StreamRequest(spikes=t) for t in trains])
    return results, time.perf_counter() - t0


def check_clean(engine, results, label: str) -> None:
    """No fallback, retry, quarantine or steady-state recompile."""
    check(
        all(r.disposition == "ok" for r in results),
        f"{label}: dispositions {[r.disposition for r in results]}",
    )
    for name in (
        "engine.faults.backend_demoted",
        "engine.faults.chunk_retries",
        "engine.requests.quarantined",
    ):
        v = counter(engine, name)
        print(f"{label}: {name} = {v:g}")
        check(v == 0, f"{label}: {name} = {v:g}")
    rc = engine.steady_state_recompiles()
    print(f"{label}: steady_state_recompiles = {rc}")
    check(rc == 0, f"{label}: {rc} steady-state recompiles")
    check(engine.backend == "fused", f"{label}: backend {engine.backend}")


def one_chip(params, cfg, trains, cache_dir: str) -> None:
    from repro.launch import serve as serve_cli
    from repro.serving.snn_engine import SNNStreamEngine

    engine = SNNStreamEngine(params, cfg, seed=1)
    print(
        f"engine: slots={engine.S} chunk_steps={engine.Tc} "
        f"capacity={engine.C} backend={engine.backend}"
    )
    check(engine.backend == "fused", f"backend resolved to {engine.backend}")

    t0 = time.perf_counter()
    compiled = (
        engine.chunk_for_timing()
        .lower(*engine.staged_chunk_args(trains[: engine.S]))
        .compile()
    )
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    print(f"chunk compile: {compile_s:.3f} s, tpu_custom_call present: "
          f"{has_kernel}")
    check(has_kernel, "compiled chunk holds no tpu_custom_call")

    results, first_s = serve(engine, trains)
    print(f"served {len(results)} requests in {first_s:.3f} s "
          f"(includes the serving chunk's compile)")
    check(len(results) == len(trains), f"served {len(results)} results")
    check_clean(engine, results, "engine")

    ref_pred, ref_out, ref_hid = reference(params, cfg, trains)
    preds = np.array([r.prediction for r in results])
    out = np.stack([r.spike_counts for r in results])
    ev = np.stack([r.events_per_layer for r in results])  # (B, layers)
    in_events = np.array([t.sum() for t in trains])
    out_dev = float(np.abs(out - ref_out).max())
    hid_dev = float(np.abs(ev[:, 1] - ref_hid).max())
    print(f"reference: predictions engine={preds.tolist()} "
          f"reference={ref_pred.tolist()}")
    print(f"reference: hidden spikes engine={ev[:, 1].tolist()} "
          f"reference={ref_hid.tolist()}")
    print(f"reference: output spikes engine={out.sum(axis=1).tolist()} "
          f"reference={ref_out.sum(axis=1).tolist()}")
    print(f"reference: largest deviation: output spike count {out_dev:g} "
          f"(tolerance {OUT_TOL:g}), hidden spike count {hid_dev:g} "
          f"(tolerance {HIDDEN_TOL:g}), layer-0 events equal "
          f"{bool(np.array_equal(ev[:, 0], in_events))}")
    check(np.array_equal(preds, ref_pred), "predictions differ")
    check(out_dev <= OUT_TOL, f"output spike counts deviate by {out_dev:g}")
    check(hid_dev <= HIDDEN_TOL, f"hidden spike counts deviate by {hid_dev:g}")
    check(np.array_equal(ev[:, 0], in_events), "layer-0 event counts differ")

    # the normal entry point, once, at the same width
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        serve_cli.main([
            "--snn", "--image-hw", "64", "--hidden", "512", "--batch", "8",
        ])
    demoted = [w for w in seen if "demoting" in str(w.message)]
    print(f"serve.py main: {time.perf_counter() - t0:.3f} s, "
          f"demotion warnings {len(demoted)}")
    check(not demoted, f"serve.py demoted: {demoted[0].message}"
          if demoted else "")
    print(f"cache: {cache_dir}")


def four_chips(params, cfg, trains) -> None:
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.serving.snn_engine import SNNStreamEngine

    n = len(jax.devices())
    check(n >= 4, f"--four-chips needs 4 devices, found {n}")
    mesh = make_host_mesh()
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    one = SNNStreamEngine(params, cfg, seed=1)
    shr = SNNStreamEngine(params, cfg, seed=1, mesh=mesh)
    ref, ref_s = serve(one, trains)
    got, got_s = serve(shr, trains)
    print(f"one chip: {len(ref)} requests in {ref_s:.3f} s; "
          f"sharded: {len(got)} in {got_s:.3f} s")
    placed = {d.id for d in shr._ring["addrs"].sharding.device_set}
    print(f"sharded ring on devices {sorted(placed)}")
    check(len(placed) == 4, f"ring placed on {sorted(placed)}")
    check_clean(one, ref, "one-chip")
    check_clean(shr, got, "sharded")
    check(len(ref) == len(got) == len(trains), "result counts differ")
    worst = 0.0
    for a, b in zip(ref, got):
        worst = max(worst, float(np.abs(a.spike_counts - b.spike_counts).max()))
        check(a.prediction == b.prediction, f"rid {a.request_id}: prediction")
        check(np.array_equal(a.spike_counts, b.spike_counts),
              f"rid {a.request_id}: spike counts")
        check(np.array_equal(a.events_per_layer, b.events_per_layer),
              f"rid {a.request_id}: events")
    print(f"sharded vs one chip: largest spike-count deviation {worst:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the slot-sharded 4-chip phase")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {d0.platform!r})",
              file=sys.stderr)
        return 1
    print(f"device: {d0.device_kind} x{len(devices)} ({d0.platform})")

    from repro.configs.collision_snn import CONFIG as cfg

    print(f"config: layers={cfg.layer_sizes} T={cfg.num_steps} "
          f"neuron={cfg.neuron_kind} reset={cfg.reset}")
    params = smoke_params(cfg)
    print(f"weights: seed {SEED}, output layer gain {OUT_GAIN:g}")
    hw = int(round(cfg.layer_sizes[0] ** 0.5))
    trains = collision_trains(NUM_REQUESTS, hw, cfg.num_steps, SEED)
    rate = float(np.mean([t.mean() for t in trains]))
    print(f"requests: {len(trains)} spike trains, mean input rate {rate:.4f}")

    try:
        if args.four_chips:
            four_chips(params, cfg, trains)
        else:
            one_chip(params, cfg, trains, cache_dir)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": d0.platform,
            "kind": d0.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
