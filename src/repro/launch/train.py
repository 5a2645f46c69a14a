"""Training launcher.

CPU-scale real run of the tiny LM or of a reduced per-family config.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch tiny-lm --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch yi-34b --smoke --steps 5
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.common.config import TrainConfig
from repro.configs import get_config, list_archs
from repro.data.pipeline import batch_iterator, make_lm_dataset
from repro.models.model_zoo import Runtime, build_model
from repro.training.trainer import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-lm", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced per-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--n-data", type=int, default=2048)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke or args.arch != "tiny-lm":
        cfg = cfg.reduced()
    cfg = cfg.with_overrides(dtype="float32")
    if cfg.family in ("vlm", "encdec"):
        raise SystemExit("use family-specific examples for vlm/encdec training")
    model = build_model(cfg)
    tcfg = TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                       decay_steps=args.steps, seed=args.seed, remat="none")
    ds = make_lm_dataset(args.n_data, args.seq, seed=args.seed)
    # clamp token ids into this model's vocab
    ds.tokens = np.minimum(ds.tokens, cfg.vocab_size - 1)
    it = batch_iterator(ds, args.batch, seed=args.seed)
    state = train_loop(model, tcfg, it, args.steps, rt=Runtime.local(),
                       ckpt_dir=args.ckpt_dir, ckpt_every=max(args.steps // 2, 1))
    print(f"finished at step {int(state.step)}")


if __name__ == "__main__":
    main()
