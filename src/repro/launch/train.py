"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
        --steps 50 --ckpt-dir /tmp/run1 [--data data, --model model]

Fault-tolerance posture (scaled down to one host, same control flow as a
1000-node launcher):
  * auto-resume: on start, the newest committed checkpoint (atomic manifest
    rename, see ckpt/manager.py) is restored — params, optimizer moments AND
    the data-pipeline cursor, so the token stream continues exactly;
  * periodic + terminal checkpoints; SIGTERM (preemption) triggers an
    immediate checkpoint before exit;
  * step retry loop: a transient step failure (in production: a failed
    all-reduce after a chip drop) restores the last checkpoint and replays;
  * elastic restart: restore() reshards to whatever mesh the relaunch got
    (tested in tests/test_ckpt.py with a shrunken data axis).
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.ckpt import manager as ckpt
from repro.data.pipeline import DataConfig, DataState, SyntheticPipeline
from repro.dist import sharding as shd
from repro.launch.mesh import make_dev_mesh
from repro.models import lm
from repro.optim import adamw
from repro.train.step import TrainConfig, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", type=int, default=1, help="data mesh axis")
    ap.add_argument("--model", type=int, default=1, help="model mesh axis")
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "const"])
    args = ap.parse_args(argv)

    spec = configs.get(args.arch)
    cfg = spec.smoke if args.smoke else spec.config
    mesh = make_dev_mesh(args.data, args.model)
    ocfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                             warmup_steps=max(args.steps // 20, 5),
                             schedule=args.schedule)
    tcfg = TrainConfig(optimizer=ocfg)

    params = lm.init_params(cfg, jax.random.key(0))
    pshard = shd.param_shardings(cfg, params, mesh)
    params = jax.device_put(params, pshard)
    opt_state = adamw.init(params)
    opt_state = jax.device_put(opt_state, adamw.state_shardings(pshard, mesh))

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)
    pipe = SyntheticPipeline(dcfg)
    dstate = DataState()

    start_step = 0
    if args.ckpt_dir:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            (params, opt_state), extra = ckpt.restore(
                args.ckpt_dir, last, (params, opt_state),
                shardings=(pshard, adamw.state_shardings(pshard, mesh)),
            )
            start_step = extra["step"]
            dstate = DataState(step=extra["data_step"])
            print(f"resumed from step {start_step}")

    step_fn = jax.jit(
        make_train_step(cfg, tcfg),
        in_shardings=(pshard, adamw.state_shardings(pshard, mesh), None),
        out_shardings=(pshard, adamw.state_shardings(pshard, mesh), None),
        donate_argnums=(0, 1),
    )

    def save(step):
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, step, (params, opt_state),
                      extra={"step": step, "data_step": dstate.step})

    interrupted = {"flag": False}

    def on_sigterm(signum, frame):
        interrupted["flag"] = True

    signal.signal(signal.SIGTERM, on_sigterm)

    t0 = time.time()
    losses = []
    step = start_step
    while step < args.steps:
        toks, labels = pipe.batch(dstate)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
        if cfg.frontend == "patch":
            batch["tokens"] = batch["tokens"][:, : args.seq - cfg.frontend_len]
            batch["prefix_embeds"] = jnp.zeros(
                (args.batch, cfg.frontend_len, cfg.d_model), jnp.bfloat16
            )
        if cfg.enc_layers:
            half = args.seq // 2
            batch["tokens"] = batch["tokens"][:, :half]
            batch["labels"] = batch["labels"][:, :half]
            batch["enc_embeds"] = jnp.zeros(
                (args.batch, args.seq - half, cfg.d_model), jnp.bfloat16
            )
        for attempt in range(3):  # step retry loop
            try:
                params, opt_state, metrics = step_fn(params, opt_state, batch)
                break
            except Exception as e:  # noqa: BLE001
                print(f"step {step} attempt {attempt} failed: {e}")
                if attempt == 2:
                    save(step)
                    raise
        dstate = pipe.advance(dstate)
        step += 1
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % 10 == 0 or step == args.steps:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({(time.time()-t0)/max(step-start_step,1):.2f}s/step)")
        if args.ckpt_dir and step % args.ckpt_every == 0:
            save(step)
        if interrupted["flag"]:
            print("SIGTERM: checkpointing and exiting")
            save(step)
            return 0
    save(args.steps)
    print(f"final loss {np.mean(losses[-10:]):.4f} "
          f"(first 10: {np.mean(losses[:10]):.4f})")
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    sys.exit(main())
