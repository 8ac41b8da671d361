"""End-to-end LM training driver example.

Default: a ~20M-param model for 200 steps (minutes on CPU).  The
documented full-size invocation trains a ~100M model for a few hundred
steps (hours on CPU; the same command drives a TPU slice):

  PYTHONPATH=src python examples/train_lm.py --full

which expands to

  python -m repro.launch.train --arch granite-8b --smoke \
      --layers 8 --d-model 768 --vocab 32768 --data uniform --pipe 4 \
      --ticks 2 --steps 300 --batch 8 --seq 256 --lr 5e-3 --mode spectrain \
      --ckpt-dir /tmp/repro_100m --resume auto
"""
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")

SMALL = ["--arch", "granite-8b", "--smoke", "--layers", "4",
         "--d-model", "256", "--vocab", "8192", "--pipe", "4",
         "--steps", "200", "--batch", "8", "--seq", "64",
         "--lr", "1e-2", "--mode", "spectrain", "--log-every", "20"]

FULL = ["--arch", "granite-8b", "--smoke", "--layers", "8",
        "--d-model", "768", "--vocab", "32768", "--data", "uniform",
        "--pipe", "4",
        "--ticks", "2", "--steps", "300", "--batch", "8", "--seq", "256",
        "--lr", "5e-3", "--mode", "spectrain",
        "--ckpt-dir", "/tmp/repro_100m", "--resume", "auto"]

if __name__ == "__main__":
    args = FULL if "--full" in sys.argv else SMALL
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    raise SystemExit(subprocess.call(
        [sys.executable, "-m", "repro.launch.train", *args], env=env))
