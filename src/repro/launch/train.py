"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m-reduced \
        --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt

Features exercised: sharded train step (pjit on the local mesh), synthetic
deterministic data stream (elastic-resume safe), async checkpointing with
atomic commits, auto-resume from the latest step, straggler monitoring,
optional int8 gradient compression (--compress, demonstration path).
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.data.lm_data import LMDataConfig, SyntheticLMStream
from repro.launch.mesh import make_local_mesh
from repro.models.frontends import batch_axes
from repro.sharding import use_mesh
from repro.sharding.partition import tree_shardings
from repro.training import checkpoint as ckpt_lib
from repro.training.optimizer import OptConfig
from repro.training.straggler import StepTimer
from repro.training.train_loop import (TrainState, abstract_train_state,
                                       init_train_state, make_train_step)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m",
                    help="arch id; '<arch>-reduced' is its tiny CPU config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if cfg.audio_frontend or cfg.num_image_tokens:
        raise SystemExit("train.py drives text archs; use examples/ for "
                         "multimodal smoke runs")
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1))

    mesh = make_local_mesh()
    data = SyntheticLMStream(LMDataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch))

    step_fn = make_train_step(cfg, opt_cfg, microbatches=args.microbatches)
    s_shapes, s_axes = abstract_train_state(cfg, opt_cfg)
    s_sh = tree_shardings(s_shapes, s_axes, mesh)

    start_step = 0
    with use_mesh(mesh):
        if args.ckpt and ckpt_lib.latest_step(args.ckpt) is not None:
            start_step = ckpt_lib.latest_step(args.ckpt)
            state = ckpt_lib.restore(s_shapes, args.ckpt, shardings=s_sh)
            print(f"resumed from step {start_step}")
        else:
            state = init_train_state(cfg, opt_cfg, jax.random.key(0))
        jit_step = jax.jit(step_fn, in_shardings=(s_sh, None),
                           out_shardings=(s_sh, None), donate_argnums=(0,))

        saver = ckpt_lib.AsyncCheckpointer(args.ckpt) if args.ckpt else None
        timer = StepTimer()
        losses = []
        for step in range(start_step, start_step + args.steps):
            batch = {k: jax.numpy.asarray(v)
                     for k, v in data.batch(step).items()}
            t0 = time.monotonic()
            state, metrics = jit_step(state, batch)
            loss = float(metrics["loss"])
            dt = time.monotonic() - t0
            if timer.observe(step, dt):
                print(f"step {step}: straggler flagged ({dt:.2f}s)")
            losses.append(loss)
            if step % args.log_every == 0:
                print(f"step {step}: loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} {dt:.2f}s")
            if saver and (step + 1) % args.ckpt_every == 0:
                saver.save(state, step + 1)
        if saver:
            saver.save(state, start_step + args.steps)
            saver.wait()
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    main()
