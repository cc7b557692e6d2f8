"""Train a language model end-to-end with the framework's training stack.

    PYTHONPATH=src python examples/train_lm.py                # CPU-sized
    PYTHONPATH=src python examples/train_lm.py --full         # ~360M config

Exercises: sharded train step, deterministic data pipeline, AdamW,
activation remat, async checkpointing + resume, straggler monitor.
The default config is CPU-budget-sized; --full selects the real smollm-360m
(use on a TPU host; a few hundred steps of the reduced config take ~a minute
here, which is the point of the example).
"""

import argparse
import sys

from repro.launch import train as train_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    arch = "smollm-360m" if args.full else "smollm-360m-reduced"
    argv = ["--arch", arch, "--steps", str(args.steps),
            "--ckpt", args.ckpt, "--ckpt-every", "50",
            "--batch", "8", "--seq", "128", "--lr", "3e-3"]
    losses = train_mod.main(argv)
    drop = losses[0] - losses[-1]
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} (-{drop:.3f}) "
          f"over {args.steps} steps; checkpoints in {args.ckpt}")


if __name__ == "__main__":
    main()
