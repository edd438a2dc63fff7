#!/usr/bin/env python3
"""Bring-up smoke test on a TPU: the system's two main paths, run once
through their normal entry points, with their results checked.

    python chip_smoke.py              # one chip: serve qwen2-0.5b with host-KV fetch
    python chip_smoke.py --chips 4    # 2x2 host: remote-DMA collectives vs XLA

One chip: qwen2-0.5b at its published widths (24 layers, d_model 896, 14
heads, 2 KV heads, d_ff 4864, vocab 151936) with seeded random weights
serves 8 requests of 1024 prompt tokens and 16 new tokens, first as misses
(prefill, then save to the host KV store) and then as hits (one upload a
context); see ``repro.launch.serve`` for the checks.  The paged decode-attention kernel is then checked against its
reference at the model's KV widths.

Four chips: the Pallas ring all-gather and all-to-all kernels, the ppermute
rings and ``CommBackend('latte')`` against XLA's collectives, bit for bit,
at 4 KB to 64 MB per device (``repro.launch.collectives_check``).

Everything runs in this one process.  The script exits non-zero, without
the final line, when JAX finds no TPU or any check fails.  Its last line is
the JSON object ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEED = 0


def check_decode_attention(cfg, *, batch: int, ctx: int, log=print) -> None:
    """The paged decode-attention kernel against its reference at the
    model's KV widths (bf16 pools, one block table per sequence)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.serve.kvcache import BLOCK_TOKENS, blocks_for_tokens

    kv, hd = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kv
    mb = blocks_for_tokens(ctx)
    n_pool = batch * mb + 3
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q = jax.random.normal(ks[0], (batch, kv, g, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (n_pool, kv, BLOCK_TOKENS, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (n_pool, kv, BLOCK_TOKENS, hd), jnp.bfloat16)
    tables = jax.random.permutation(ks[3], n_pool)[:batch * mb].reshape(batch, mb)
    lengths = jnp.asarray(np.linspace(1, ctx, batch), jnp.int32)
    out = decode_attention(q, kp, vp, tables.astype(jnp.int32), lengths)
    ref = paged_decode_attention_ref(q, kp, vp, tables, lengths)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    # The bound the interpret-mode tests hold the kernel to in bf16: both
    # sides round their output to bf16 (2^-8 relative), and the reference's
    # matmuls may take bf16 passes on the chip.
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)
    log(f"[kernel] paged decode attention, {batch} sequences up to {ctx} tokens, "
        f"KV {kv} x head_dim {hd}, {g} query heads per KV head: max |err| vs "
        f"reference {np.max(np.abs(out - ref)):.3e} (bound 2e-2 + 2e-2 |ref|, bf16)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the collectives phase on a 2x2 host")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import compile_stats, enable_compile_cache

    cache = enable_compile_cache()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    print(f"[cache] persistent compilation cache: {cache}")
    t0 = time.perf_counter()

    if args.chips == 4:
        from repro.launch import collectives_check
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4,), ("x",), devices=devices[:4])
        n = collectives_check.run(mesh, "x", seed=SEED)
        print(f"[collectives] {n} comparisons, all bit-identical to XLA")
    else:
        from repro.configs import get_config
        from repro.launch import serve

        cfg = get_config("qwen2-0.5b")
        serve.run(cfg, batch=8, ctx=1024, new=16, seed=SEED)
        check_decode_attention(cfg, batch=8, ctx=1024)

    cs = compile_stats()
    print(f"[done] {time.perf_counter() - t0:.1f} s wall; compilation: "
          f"{cs['compiles']} programs, {cs['compile_s']:.1f} s, "
          f"{cs['cache_hits']} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
