"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --steps 100 \
        [--smoke] [--batch 8] [--seq 128] [--ckpt-dir /tmp/ckpt]

``--smoke`` uses the reduced same-family config (CPU-runnable); the full
configs are meant for the production mesh (see dryrun.py for the
lower/compile proof on 256/512 chips).
"""
from __future__ import annotations

import argparse

from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.data.synthetic import SyntheticConfig, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.train.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ALL_ARCHS), default="gpt3-24l")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--noise", type=float, default=0.05)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    loader = SyntheticLM(SyntheticConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, noise=args.noise,
        ext_embed_dim=cfg.ext_embed_dim, seed=args.seed))
    tcfg = TrainConfig(steps=args.steps, lr=args.lr,
                       microbatches=args.microbatches,
                       ckpt_dir=args.ckpt_dir or "/tmp/repro_ckpt",
                       ckpt_every=args.ckpt_every, seed=args.seed)
    trainer = Trainer(cfg, tcfg, loader)
    if args.ckpt_every:
        trainer.maybe_restore()
    print(f"training {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size} (optimal loss ≈ "
          f"{loader.optimal_loss():.3f})")
    trainer.fit()


if __name__ == "__main__":
    main()
