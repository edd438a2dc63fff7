"""Batched LLM serving engine with host-memory context caching.

Serving flow (mirrors the paper's vLLM + KV-offload setup, §5.3):

1. A request arrives with a context key.  On a HOST CACHE MISS the engine
   runs prefill on device, emits the first token, and SAVES the paged cache
   state to the host store: the model's own per-layer leaves with a token
   axis, K and V or multi-head latent attention's latent and rotated key.
   On a HIT it FETCHES those blocks to the device in one upload a context
   and emits the first token with a single decode step — no prefill
   compute.  Either way one program, ``jit_rebuild``, lays out the decode
   cache from cache state already on the device: a hit's fetched contexts,
   or a miss's prefill state.
2. Decode proceeds in batched steps over all active sequences.

TTFT therefore = fetch(+rebuild) time on hits vs prefill time on misses —
exactly the quantity Figures 16/17 study.  Wall times are host-clock spans
that end in a device-to-host copy of the result (so the device has
finished).

Each layer boundary opens a ``jax.profiler.TraceAnnotation``, which costs
about a microsecond and records only while the profiler runs, on the clock
of the device trace: ``serve.generate`` > ``serve.first_token`` (the TTFT
interval; ``batch``, ``hit``, ``cache``: ``kv`` or ``latent``) >
``serve.kv.fetch`` (``host_store.py``), ``serve.cache.build``
(the dispatch of the rebuild program), ``serve.step.first`` on a hit,
``serve.prefill`` > ``serve.kv.pull`` on a miss; then ``serve.kv.save`` and
``serve.cache.build`` (miss), ``serve.first_logits`` and ``serve.decode``.
No span opens per decoded token.  The decode step takes its cache by
donation and writes each token where the cache lies.  Byte, batch, sync,
in-place step and route totals go to ``repro.serve.counters``; a MoE
model's routes are summed on the device over a batch's decode steps and
read once after the last.

The modeled MI300X server for load studies is ``ServingSimulator`` in
``core/serving_model.py`` (DESIGN.md §12).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod, moe as moe_mod
from repro.models.transformer import Model
from . import counters
from .host_store import HostKVStore
from .kvcache import to_blocks


# Decode caches hold whole tiles of 8 slots, the TPU's sublane tile.  The v5e
# lays out a latent cache [L, B, capacity, R] with its slots down the tiles
# only where they fill them, and across the batch otherwise; the attention
# dots read a layer in place only in the first layout.
SLOT_TILE = 8


def decode_capacity(slots: int) -> int:
    """The capacity of the decode caches the engine lays out for ``slots``
    slots: whole tiles of ``SLOT_TILE``."""
    return -(-slots // SLOT_TILE) * SLOT_TILE


def _consumed(cache) -> int:
    """1 if a decode step took ``cache``'s buffers by donation, else 0."""
    return int(jax.tree.leaves(cache)[0].is_deleted())


@dataclasses.dataclass
class RequestStats:
    key: str
    cache_hit: bool
    ttft_wall_s: float          # the batch's wall time to its first tokens
    prompt_tokens: int


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # [B, n_new]
    first_logits: np.ndarray    # [B, vocab] f32 logits of the first new token
    request_stats: list[RequestStats]
    decode_wall_s: float
    tokens_per_s_wall: float


class ServeEngine:
    def __init__(self, model: Model, params, *, host_store: HostKVStore | None = None):
        cfg = model.cfg
        if cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(f"serving engine supports decoder-LM families, got {cfg.family}")
        if model.scan_info.get("per_unit", 1) != 1:
            raise ValueError("serving engine requires per_unit==1 layer stacking")
        self.model = model
        self.params = params
        self.store = host_store or HostKVStore()

        def prefill(p, b):
            return model.forward(p, b, want_cache=True, remat=False)

        def rebuild(parts, n_tokens, capacity):
            # cache state parts, leaves [L, b, S', ...] -> the decode caches.
            # Joined batch-major: the v5e compiler then writes each part into
            # the cache's layout as it reads it; joined on axis 1, it first
            # copies each [L, 1, S', ...] part into that layout (a moonlight
            # hit batch: 2.6 GB of temporaries against 1.0 GB).
            state = {name: jnp.concatenate([p[name].swapaxes(0, 1) for p in parts])
                     .swapaxes(0, 1)[:, :, :n_tokens] for name in parts[0]}   # [L, B, S, ...]
            return model.caches_from_layers(jax.vmap(lambda st: attn_mod.prefill_cache(
                model.cfg, st, capacity))(state))

        self._prefill_jit = jax.jit(prefill)
        # the step writes one token into the cache it is given: donated,
        # its output is the input's own buffers
        self._decode_jit = jax.jit(model.decode_step, donate_argnames="caches")
        self._rebuild_jit = jax.jit(rebuild, static_argnames=("n_tokens", "capacity"))

    # ----------------------------------------------------------- helpers ----
    def _prefill(self, prompts: jax.Array):
        """-> (logits, the cache state on the device and its copy on the
        host: leaves [L, B, S, ...])."""
        with jax.profiler.TraceAnnotation("serve.prefill"):
            logits, _, states = self._prefill_jit(self.params, {"tokens": prompts})
            state, = states      # per_unit == 1
            with jax.profiler.TraceAnnotation("serve.kv.pull"):
                pulled = {name: np.asarray(a) for name, a in state.items()}
        counters.add("kv.pull.to_host_bytes", sum(a.nbytes for a in pulled.values()))
        return logits, state, pulled

    def _rebuild_cache(self, parts: list[dict[str, jax.Array]], n_tokens: int,
                       capacity: int):
        """Cache state on the device, the batch in ``parts`` (leaves
        [L, b, S', ...], concatenated on b) -> the decode caches at
        ``capacity`` of its first ``n_tokens`` tokens, laid out by one
        program on the device."""
        with jax.profiler.TraceAnnotation("serve.cache.build"):
            caches = self._rebuild_jit(parts, n_tokens=n_tokens, capacity=capacity)
        counters.add("cache.rebuild.batches", 1)
        return caches

    # ------------------------------------------------------------ public ----
    def first_token(self, prompts: np.ndarray, keys: Sequence[str],
                    *, capacity: int | None = None):
        """TTFT path for a batch sharing prompt length, with a decode cache
        of at least ``capacity`` slots (``decode_capacity``).  Returns
        (first_tokens [B], first_logits [B, vocab] f32, cache, stats)."""
        B, S = prompts.shape
        capacity = decode_capacity(capacity or S + 64)
        all_hit = all(k in self.store for k in keys)
        cache_kind = "latent" if self.model.cfg.mla else "kv"
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.first_token", batch=B, hit=all_hit,
                                          cache=cache_kind):
            if all_hit:
                n_tokens = {self.store.tokens_for(key) for key in keys}
                if len(n_tokens) != 1:
                    raise ValueError(f"a hit batch's contexts differ in length: {n_tokens}")
                fetched = [self.store.fetch(key) for key in keys]
                built = self._rebuild_cache(fetched, n_tokens.pop(), capacity)
                with jax.profiler.TraceAnnotation("serve.step.first"):
                    logits, cache = self._decode_jit(
                        self.params,
                        {"tokens": jnp.asarray(prompts[:, -1:]), "pos": jnp.int32(S - 1)},
                        built)
                    first = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
                counters.add("decode.cache.in_place_steps", _consumed(built))
            else:
                logits, state, pulled = self._prefill(jnp.asarray(prompts))
                first = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            wall = time.perf_counter() - t0
        stats = [RequestStats(key, all_hit, wall, S) for key in keys]
        if not all_hit:
            for b, key in enumerate(keys):
                with jax.profiler.TraceAnnotation("serve.kv.save"):
                    self.store.save(key, {name: to_blocks(a[:, b:b + 1])
                                          for name, a in pulled.items()}, S)
            cache = self._rebuild_cache([state], S, capacity)
        with jax.profiler.TraceAnnotation("serve.first_logits"):
            first_logits = np.asarray(logits[:, -1], np.float32)
        return first, first_logits, cache, stats

    def generate(self, prompts: np.ndarray, keys: Sequence[str],
                 n_new: int) -> GenerationResult:
        B, S = prompts.shape
        capacity = S + n_new + 1
        with jax.profiler.TraceAnnotation("serve.generate"):
            first, first_logits, cache, stats = self.first_token(
                prompts, keys, capacity=capacity)
            toks = [first]
            cur = jnp.asarray(first)[:, None]
            in_place = 0
            with jax.profiler.TraceAnnotation("serve.decode"):
                t0 = time.perf_counter()
                for i in range(n_new - 1):
                    prev = cache
                    logits, cache = self._decode_jit(
                        self.params, {"tokens": cur, "pos": jnp.int32(S + i)}, prev)
                    in_place += _consumed(prev)
                    cur = jnp.argmax(logits[:, -1], axis=-1)[:, None]
                    toks.append(np.asarray(cur)[:, 0])
                dt = time.perf_counter() - t0
        counters.add("decode.host_syncs", n_new - 1)
        counters.add("decode.cache.in_place_steps", in_place)
        counters.add("moe.held_dense.steps",
                     n_new - 1 if moe_mod.held_dense(self.model.cfg, B) else 0)
        if "routes" in cache:
            routes, local = np.asarray(cache["routes"])
            counters.add("moe.routes", routes)
            counters.add("moe.routes.local", local)
        tokens = np.stack(toks, axis=1)
        return GenerationResult(tokens, first_logits, stats, dt,
                                B * (n_new - 1) / max(dt, 1e-9))
