"""End-to-end training driver: train a reduced assigned-architecture LM on
the deterministic synthetic pipeline with checkpoint/resume.

  PYTHONPATH=src python examples/train_lm.py --arch qwen3-1.7b --steps 100
  (add --no-reduced on a real pod to train the full config)
"""

import argparse
import sys

sys.path.insert(0, "src")

from repro.launch import compile_cache
from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--no-reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    args = ap.parse_args()
    compile_cache.enable()

    argv = ["--arch", args.arch, "--steps", str(args.steps),
            "--batch", "8", "--seq", "64", "--ckpt-dir", args.ckpt_dir,
            "--ckpt-every", "25"]
    if not args.no_reduced:
        argv.append("--reduced")
    return train_main(argv)


if __name__ == "__main__":
    sys.exit(main())
