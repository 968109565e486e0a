"""Training launcher: any assigned arch (reduced or full) or the paper's
SNN, with checkpoint/restart, straggler watchdog and host-mesh sharding.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --reduced --steps 50 --ckpt /tmp/ckpt --resume auto

Event-driven SNN training (surrogate gradients through the AER gather
path, synthetic DVS collision workload, energy-aware loss):

  PYTHONPATH=src python -m repro.launch.train --snn-events --steps 100 \
      --batch 32 --image-hw 32 --snn-steps 15 --energy-lambda 0.05 \
      [--polarity two_channel|signed|on_only] [--ckpt /tmp/snn_ev]

Observability (any mode, mirroring launch/serve.py): ``--metrics-json``
dumps the trainer's registry snapshot (step-time/loss/grad-norm
histograms, per-layer spike + energy counters for --snn-events),
``--trace-out`` writes the per-window span trace as Perfetto-loadable
Chrome trace JSON, ``--timeseries-out`` the per-window time series as
JSONL.

On a real TPU pod this same entry point runs under
`make_production_mesh()`; on this CPU container it uses the host mesh
(1 device) with identical code paths — the production mesh is exercised
by launch/dryrun.py.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.data.tokens import MarkovTokenStream, TokenStreamConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import CLIP_EMBED_DIM, Model
from repro.optim import adamw, chain_clip, warmup_cosine
from repro.train.loop import Trainer


def batches(cfg, batch_size, seq_len):
    stream = MarkovTokenStream(
        TokenStreamConfig(
            vocab_size=cfg.vocab_size, seq_len=seq_len, batch_size=batch_size
        )
    )
    import numpy as np

    rng = np.random.default_rng(0)
    for x, y in stream.batches():
        if cfg.num_codebooks:
            x = np.stack([x] * cfg.num_codebooks, -1)
            y = np.stack([y] * cfg.num_codebooks, -1)
        b = {"tokens": jnp.asarray(x), "targets": jnp.asarray(y)}
        if cfg.num_image_tokens:
            b["img_embeds"] = jnp.asarray(
                rng.normal(0, 1, (batch_size, cfg.num_image_tokens,
                                  CLIP_EMBED_DIM)).astype(np.float32)
            )
        yield b


def _train_snn_events(args) -> None:
    from repro.sparse_train import trainer as ev_trainer

    tcfg = ev_trainer.EventTrainConfig(
        image_hw=args.image_hw,
        num_steps=args.snn_steps,
        hidden=args.hidden,
        polarity_mode=args.polarity,
        quant_q115=(args.quant == "q115"),
    )
    trainer = ev_trainer.EventTrainer(
        tcfg,
        energy_lambda=args.energy_lambda,
        lr=args.lr if args.lr is not None else 5e-4,
        ckpt_dir=args.ckpt,
        ckpt_every=25,
        accum_steps=args.accum,
        seed=args.seed,
    )
    print(
        f"snn-events: {tcfg.input_size}-{tcfg.hidden}-2 "
        f"(dvs {tcfg.image_hw}x{tcfg.image_hw}, "
        f"polarity={tcfg.polarity_mode}, T={tcfg.num_steps}, "
        f"energy_lambda={args.energy_lambda}, "
        f"params={trainer.model.param_count()/1e3:.1f}K)"
    )
    if args.ckpt and args.resume == "auto":
        state = trainer.restore_or_init(jax.random.PRNGKey(args.seed))
        if int(state.step):
            print(f"resumed at step {int(state.step)}")
    else:
        state = trainer.init_state(jax.random.PRNGKey(args.seed))

    mesh = make_host_mesh()
    with mesh:
        # fast-forward the data stream to the restored step so a resumed
        # run sees bit-identical batches to an uninterrupted one
        state, metrics = trainer.run(
            state,
            ev_trainer.dvs_batches(
                args.seed, args.batch, tcfg, start_step=int(state.step)
            ),
            args.steps,
        )
    print("final:", metrics)
    trainer.export_obs(
        metrics_json=args.metrics_json,
        trace_out=args.trace_out,
        timeseries_out=args.timeseries_out,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b",
                    choices=configs.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config of the same family")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=None,
                    help="learning rate (default: 3e-4 for LM archs, the "
                         "paper's 5e-4 for --snn-events)")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", default="auto", choices=["auto", "never"])
    ap.add_argument("--quant", default=None, choices=[None, "q115"])
    ap.add_argument("--seed", type=int, default=0)
    # event-driven SNN training mode
    ap.add_argument("--snn-events", action="store_true",
                    help="train the SNN event-drivenly on synthetic DVS "
                         "collision streams (sparse_train subsystem)")
    ap.add_argument("--image-hw", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--snn-steps", type=int, default=15,
                    help="SNN coding window (time steps)")
    ap.add_argument("--energy-lambda", type=float, default=0.0,
                    help="weight of the energy regularizer (loss/nJ)")
    ap.add_argument("--polarity", default="two_channel",
                    choices=["two_channel", "signed", "on_only"],
                    help="how DVS ON/OFF events map onto input weights")
    # observability (any mode; mirrors launch/serve.py)
    ap.add_argument("--metrics-json", default=None,
                    help="write the trainer's metrics-registry snapshot "
                         "(histograms/counters/gauges) to this path")
    ap.add_argument("--trace-out", default=None,
                    help="write per-window train spans as Chrome "
                         "trace-event JSON (Perfetto-loadable)")
    ap.add_argument("--timeseries-out", default=None,
                    help="write the per-window time series (counter "
                         "deltas, windowed rates) as JSONL")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.snn_events:
        _train_snn_events(args)
        return

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.quant:
        import dataclasses

        cfg = dataclasses.replace(cfg, quant=args.quant)
    model = Model(cfg)
    print(f"arch={args.arch} params={model.param_count()/1e6:.1f}M "
          f"(active {model.active_param_count()/1e6:.1f}M)")

    opt = chain_clip(
        adamw(warmup_cosine(args.lr if args.lr is not None else 3e-4,
                            10, max(args.steps, 11))), 1.0
    )
    trainer = Trainer(
        model, opt, ckpt_dir=args.ckpt, ckpt_every=25, accum_steps=args.accum
    )
    if args.ckpt and args.resume == "auto":
        state = trainer.restore_or_init(jax.random.PRNGKey(0))
        if int(state.step):
            print(f"resumed at step {int(state.step)}")
    else:
        state = trainer.init_state(jax.random.PRNGKey(0))

    mesh = make_host_mesh()
    with mesh:
        state, metrics = trainer.run(
            state, batches(cfg, args.batch, args.seq), args.steps
        )
    print("final:", metrics)
    trainer.export_obs(
        metrics_json=args.metrics_json,
        trace_out=args.trace_out,
        timeseries_out=args.timeseries_out,
    )


if __name__ == "__main__":
    main()
