"""Serving driver: batched requests with host-memory context caching (the
paper's §5.3 workload).

Serves the architecture at its published widths with seeded random
weights: a batch of requests first misses (prefill, then save to the host
store), then hits twice (one upload a context from the host store).  It
checks that the fetch moves exactly the bytes that were saved, that the
two hits decode the same tokens, and that the hit path's first-token
logits agree with the miss path's; it reports how many of the hit's
tokens equal the miss's.  Times are host-clock wall times on the device
JAX runs on; a warm-up pass on other keys compiles every program first.
Each batch also reports what ``repro.serve.counters`` counted while it
ran: bytes moved each way by the fetch or the prefill K/V pull, caches
laid out on the device, and host syncs in decode; in a MoE model also its
decode routes, those to the held experts, and the decode steps whose
expert layers took the dense form.

    python -m repro.launch.serve                      # qwen2-0.5b, 8 x 1024 tokens
    JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced --batch 2 --ctx 64

(with ``PYTHONPATH=src``).  ``--reduced`` serves the toy preset of the same
family (width <= 256, <= 2 layers), for the CPU.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.serve.counters import counters
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import layer_major
from .compile_cache import compile_stats, enable_compile_cache

# Hit and miss compute the prompt's last position in differently shaped
# bf16 programs (one decode step vs the whole-prompt prefill), which round
# at different points in each of the layers.  That moves the logits by a
# few bf16 ulps (2^-8 relative) per layer; a cache holding the wrong
# tokens, positions or blocks moves them by O(1).
LOGIT_REL_TOL = 5e-2


def _bit_equal(a: np.ndarray, b: np.ndarray) -> bool:
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


def _describe(cfg: ArchConfig) -> str:
    return (f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.n_heads} heads ({cfg.n_kv_heads} KV, head_dim {cfg.head_dim}), "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.n_params / 1e6:.0f}M params")


def run(cfg: ArchConfig, *, batch: int, ctx: int, new: int, seed: int = 0,
        log=print) -> None:
    """Serve ``batch`` requests of ``ctx`` prompt tokens and ``new`` decoded
    tokens as misses, then as hits, and check the results.  Raises
    AssertionError if a check fails."""
    model = build_model(cfg)
    eng = ServeEngine(model, jax.jit(model.init)(jax.random.PRNGKey(seed)))
    rng = np.random.default_rng(seed)
    dev = jax.devices()[0]
    where = f"wall, {dev.platform}:{dev.device_kind}"
    log(f"[serve] {_describe(cfg)}")
    log(f"[serve] {batch} requests x {ctx} prompt tokens, {new} new tokens each")

    def counted(prompts, keys):
        """(generation, counter deltas) of one batch."""
        c0 = counters()
        res = eng.generate(prompts, keys, new)
        return res, {k: n - c0.get(k, 0) for k, n in counters().items()}

    def one_pass(tag: str):
        prompts = rng.integers(0, cfg.vocab, (batch, ctx)).astype(np.int32)
        keys = [f"{tag}-{i}" for i in range(batch)]
        return (keys, counted(prompts, keys), counted(prompts, keys),
                eng.generate(prompts, keys, new))

    t0 = time.perf_counter()
    one_pass("warmup")
    warmup_s = time.perf_counter() - t0
    cs = compile_stats()
    log(f"[serve] warm-up pass on other keys: {warmup_s:.3f} s ({where}); "
        f"compilation so far: {cs['compiles']} programs, {cs['compile_s']:.3f} s, "
        f"{cs['cache_hits']} persistent-cache hits")

    keys, (miss, cn), (hit, ch), again = one_pass("req")
    assert not miss.request_stats[0].cache_hit and hit.request_stats[0].cache_hit
    st = miss.request_stats[0]
    log(f"[miss] TTFT {st.ttft_wall_s * 1e3:.3f} ms, decode "
        f"{miss.tokens_per_s_wall:.1f} tok/s ({where}); cache state pulled to the host "
        f"{cn['kv.pull.to_host_bytes']} B, {cn['cache.rebuild.batches']} cache laid "
        f"out on the device, {cn['decode.host_syncs']} decode syncs")
    if cfg.moe:
        log(f"[miss/moe] {cn['moe.routes']} decode routes, {cn['moe.routes.local']} "
            f"to held experts; {cn['moe.held_dense.steps']} decode steps through the "
            f"dense expert form")
    st = hit.request_stats[0]
    log(f"[hit/b2b] TTFT {st.ttft_wall_s * 1e3:.3f} ms, decode "
        f"{hit.tokens_per_s_wall:.1f} tok/s ({where}); fetch "
        f"{ch['kv.fetch.to_device_bytes']} B to the device and 0 B back, "
        f"{ch['cache.rebuild.batches']} cache laid out on the device, "
        f"{ch['decode.host_syncs']} decode syncs")

    for key in keys:
        saved = eng.store.saved(key)
        got = eng.store.fetch(key)
        assert all(_bit_equal(np.asarray(got[name])[:, 0], layer_major(blocks))
                   for name, blocks in saved.items()), \
            f"fetched a cache state that differs from what {key} saved"
    log(f"[check] fetched cache state bit-identical to the saved state "
        f"({', '.join(saved)}): {len(keys)} requests, "
        f"{sum(a.nbytes for a in saved.values())} bytes each")

    # Not asserted against the miss: at published widths the hit's and the
    # miss's bf16 logits differ by a few ulps (below), enough to break a
    # near tie between two tokens, and the decode then follows the other.
    assert np.array_equal(again.tokens, hit.tokens), "a second hit decoded other tokens"
    log(f"[check] a second hit decoded the first hit's tokens "
        f"({hit.tokens.shape[0]} x {hit.tokens.shape[1]}); "
        f"{int(np.sum(hit.tokens == miss.tokens))}/{hit.tokens.size} equal the miss's")

    got, ref = hit.first_logits, miss.first_logits
    assert np.isfinite(got).all() and np.isfinite(ref).all(), "non-finite logits"
    rel = float(np.max(np.linalg.norm(got - ref, axis=-1)
                       / np.linalg.norm(ref, axis=-1)))
    agree = int(np.sum(got.argmax(-1) == ref.argmax(-1)))
    assert rel <= LOGIT_REL_TOL, f"hit vs miss logits: relative error {rel} > {LOGIT_REL_TOL}"
    log(f"[check] hit vs miss first-token logits: worst relative L2 error "
        f"{rel:.3e} <= {LOGIT_REL_TOL} (bf16); argmax agrees for {agree}/{batch}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list(ARCH_IDS))
    ap.add_argument("--reduced", action="store_true",
                    help="serve the toy preset of the family (CPU use)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ctx", type=int, default=1024)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    run(cfg, batch=args.batch, ctx=args.ctx, new=args.new, seed=args.seed)


if __name__ == "__main__":
    main()
