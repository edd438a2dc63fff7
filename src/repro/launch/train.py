"""Training driver.

Real execution runs the REDUCED variant of any assigned arch on the local
device(s); the FULL configs are exercised via the dry-run (lowering only).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --steps 100
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import DataConfig, data_iterator
from repro.models import build_model
from repro.train.checkpoint import save_checkpoint
from repro.train.loop import train_loop
from repro.train.optimizer import AdamWConfig
from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--history-out", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    if cfg.family in ("vlm",):
        raise SystemExit("use the dry-run for VLM training shapes (stub frontend)")
    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch, seed=args.seed)
    it = data_iterator(dc)
    if cfg.family == "audio":
        base = it

        def with_feats(gen):
            rng = jax.random.PRNGKey(args.seed)
            for b in gen:
                feats = jax.random.normal(
                    rng, (args.batch, cfg.encdec.encoder_seq, cfg.d_model))
                yield dict(b, encoder_feats=feats)

        it = with_feats(base)

    opt = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1),
                      total_steps=args.steps)

    def log(i, m):
        print(f"step {m['step']:5d} loss {m['loss']:.4f} gnorm {m['grad_norm']:.3f} "
              f"lr {m['lr']:.2e} wall {m['wall_s']:.1f}s")

    state, history = train_loop(model, it, steps=args.steps, opt_cfg=opt,
                                rng=jax.random.PRNGKey(args.seed), callback=log)
    assert history[-1]["loss"] < history[0]["loss"], "training failed to reduce loss"
    if args.checkpoint:
        save_checkpoint(args.checkpoint, state, step=args.steps)
        print(f"saved checkpoint to {args.checkpoint}")
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)


if __name__ == "__main__":
    main()
