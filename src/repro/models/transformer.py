"""Model assembly: builds init/forward/decode functions for every assigned
architecture family (dense / moe / vlm / ssm / hybrid / audio).

Conventions
-----------
* Params are nested dicts; repeated layers are STACKED along a leading scan
  axis and executed with ``jax.lax.scan`` (keeps HLO size and compile time
  independent of depth — required for 80-layer configs on this container).
* ``forward``  : full-sequence (train / prefill).  Returns (logits, aux, caches)
  where caches is None unless ``want_cache`` (prefill).
* ``decode_step``: ONE new token against per-layer caches/states.
* ``ac(x, kind)`` is an optional activation-sharding hook threaded from the
  launcher (identity by default) — models stay mesh-agnostic.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import attention as attn
from . import layers as L
from . import mamba2, mla, moe as moe_mod, rwkv6


def _identity_ac(x, kind):  # default activation-sharding hook
    return x


def scan_blocks(body, carry, xs, unroll: bool = False):
    """lax.scan over stacked layer params, or a python unroll.

    The unrolled form exists for the roofline costing pass: XLA's
    ``cost_analysis`` counts a while body ONCE regardless of trip count
    (verified empirically — DESIGN.md §7), so exact per-layer costs are
    measured by lowering small UNROLLED variants and differencing.
    """
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    leaves = jax.tree.leaves(xs)
    n = leaves[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = ys[0] if ys else None
    return carry, stacked


# --------------------------------------------------------------------------
# Standard transformer block (dense / moe / vlm)
# --------------------------------------------------------------------------
def init_tf_block(cfg: ArchConfig, rng: jax.Array, *, dense: bool = False) -> dict:
    """One decoder block; ``dense`` gives it a dense MLP in a MoE model (a
    leading dense layer)."""
    k1, k2 = jax.random.split(rng)
    p = {
        "ln1": L.init_norm(cfg, cfg.d_model),
        "attn": mla.init_mla(cfg, k1) if cfg.mla else attn.init_attention(cfg, k1),
        "ln2": L.init_norm(cfg, cfg.d_model),
    }
    if cfg.moe and not dense:
        p["moe"] = moe_mod.init_moe(cfg, k2)
    else:
        p["mlp"] = L.init_mlp(cfg, k2, cfg.d_model, cfg.d_ff)
    return p


def _ffn(cfg, p, h, ac, expert_sharding):
    """The block's MLP or MoE: (output, aux loss, routes to held experts)."""
    if "moe" in p:
        es = expert_sharding or (lambda t: ac(t, "expert"))
        return moe_mod.apply_moe(cfg, p["moe"], h, expert_sharding=es)
    return L.apply_mlp(cfg, p["mlp"], h), jnp.float32(0), jnp.int32(0)


def _attn_ctx(cfg, p, h, *, rope, window, return_state=False):
    """Attention over the whole context; with ``return_state`` also the
    layer's cache leaves [B, S, ...]: K/V, or MLA's latent and rotated key."""
    if cfg.mla:
        return mla.mla_ctx(cfg, p, h, rope=rope, return_state=return_state)
    out = attn.attention_ctx(cfg, p, h, rope=rope, causal=True, window=window,
                             return_kv=return_state)
    if return_state:
        out, (k, v) = out
        return out, {"k": k, "v": v}
    return out


def apply_tf_block(cfg, p, x, *, rope, window, ac, expert_sharding=None):
    h = L.apply_norm(cfg, p["ln1"], x)
    out = _attn_ctx(cfg, p["attn"], h, rope=rope, window=window)
    x = x + ac(out, "partial")
    x = ac(x, "hidden_mid")
    h = L.apply_norm(cfg, p["ln2"], x)
    y, aux, _ = _ffn(cfg, p, h, ac, expert_sharding)
    x = ac(x + ac(y, "partial"), "hidden")
    return x, aux


def apply_tf_block_prefill(cfg, p, x, *, rope, window, ac, expert_sharding=None):
    """Like apply_tf_block but also returns this layer's cache state."""
    h = L.apply_norm(cfg, p["ln1"], x)
    out, state = _attn_ctx(cfg, p["attn"], h, rope=rope, window=window, return_state=True)
    x = ac(x + out, "hidden_mid")
    h = L.apply_norm(cfg, p["ln2"], x)
    y, aux, _ = _ffn(cfg, p, h, ac, expert_sharding)
    return ac(x + y, "hidden"), aux, state


def apply_tf_block_decode(cfg, p, x, cache, layer, pos, *, rope_fn, window, ac,
                          expert_sharding=None):
    """One token through the block whose cache is ``layer`` of the stacked
    ``cache`` -> (x, the stack with the token written, routes to held
    experts)."""
    h = L.apply_norm(cfg, p["ln1"], x)
    if cfg.mla:
        out, cache = mla.mla_decode(cfg, p["attn"], h, cache, pos, rope_fn=rope_fn,
                                    at=(layer,))
    else:
        out, cache = attn.attention_decode(cfg, p["attn"], h, cache, pos,
                                           rope_fn=rope_fn, window=window, at=(layer,))
    x = x + out
    h = L.apply_norm(cfg, p["ln2"], x)
    y, _, n_local = _ffn(cfg, p, h, ac, expert_sharding)
    return x + y, cache, n_local


# --------------------------------------------------------------------------
# Rope helpers
# --------------------------------------------------------------------------
def make_rope(cfg: ArchConfig, positions: jax.Array):
    """positions: [B,S] (rope) or [3,B,S] (mrope).  Returns (cos, sin) or None."""
    if cfg.rope_kind == "rope":
        dim = cfg.mla.qk_rope_head_dim if cfg.mla else cfg.head_dim
        return L.rope_angles(positions, dim, cfg.rope_theta)
    if cfg.rope_kind == "mrope":
        if positions.ndim == 2:  # text-only: t=h=w
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return L.mrope_angles(positions, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections)
    return None


def make_rope_fn(cfg: ArchConfig):
    if cfg.rope_kind in ("rope", "mrope"):
        return lambda pos_b: make_rope(cfg, pos_b)
    return None


def _layer_windows(cfg: ArchConfig) -> list[int | None]:
    """Per-scan-unit attention windows (gemma2 alternates local/global)."""
    if cfg.layer_pattern:
        return [cfg.sliding_window if kind == "local" else None
                for kind in cfg.layer_pattern]
    return [cfg.sliding_window]


# --------------------------------------------------------------------------
# Model bundle
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    init: Callable[[jax.Array], Any]
    forward: Callable[..., Any]          # (params, batch, ac=..., want_cache=False)
    decode_step: Callable[..., Any]      # (params, batch, caches, ac=...)
    init_caches: Callable[..., Any]      # (batch_size, capacity)
    scan_info: dict                      # cost scopes: {"layer_trip": L, ...}
    # decoder LMs: every layer's attention cache stacked [L, ...] -> the
    # caches decode_step takes
    caches_from_layers: Callable[..., Any] | None = None

    def loss(self, params, batch, ac=_identity_ac, unroll=False):
        logits, aux, _ = self.forward(params, batch, ac=ac, unroll=unroll)
        labels = batch.get("labels")
        if labels is None:
            labels = batch["tokens"][:, 1:]
            logits = logits[:, :-1]
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - ll) + aux


def build_model(cfg: ArchConfig) -> Model:
    if cfg.family in ("dense", "moe", "vlm"):
        return _build_decoder_lm(cfg)
    if cfg.family == "ssm":
        return _build_rwkv(cfg)
    if cfg.family == "hybrid":
        return _build_zamba(cfg)
    if cfg.family == "audio":
        return _build_whisper(cfg)
    raise ValueError(cfg.family)


def _stack_init(init_one: Callable, rng: jax.Array, n: int):
    keys = jax.random.split(rng, n)
    return jax.vmap(init_one)(keys)


# ======================================================================
# Dense / MoE / VLM decoder-only LM
# ======================================================================
def _build_decoder_lm(cfg: ArchConfig) -> Model:
    """Decoder LM: ``first_dense_layers`` leading blocks with a dense MLP
    (``dense_blocks``), then the scanned units (``blocks``).  Decode caches
    are ``{"blocks": tuple(per_unit)}``, with ``"dense"`` beside it where
    there are leading dense layers and ``"routes"`` (int32 [2]: every route
    and the routes to held experts, summed over decode steps) in a MoE model."""
    pattern = cfg.layer_pattern or ("layer",)
    per_unit = len(pattern)
    n_dense = cfg.first_dense_layers
    assert (cfg.n_layers - n_dense) % per_unit == 0 and (n_dense == 0 or per_unit == 1)
    trip = (cfg.n_layers - n_dense) // per_unit
    windows = _layer_windows(cfg)
    init_cache = mla.init_mla_cache if cfg.mla else attn.init_attn_cache

    def init(rng):
        k_e, k_b, k_u = jax.random.split(rng, 3)

        def init_unit(k):
            ks = jax.random.split(k, per_unit)
            return tuple(init_tf_block(cfg, ks[i]) for i in range(per_unit))

        p = {
            "embed": L.init_embedding(cfg, k_e),
            "blocks": _stack_init(init_unit, k_b, trip),
            "final_norm": L.init_norm(cfg, cfg.d_model),
        }
        if n_dense:
            p["dense_blocks"] = _stack_init(lambda k: (init_tf_block(cfg, k, dense=True),),
                                            jax.random.fold_in(k_b, 1), n_dense)
        if not cfg.tie_embeddings:
            p["unembed"] = (jax.random.normal(k_u, (cfg.d_model, cfg.vocab),
                                              jnp.dtype(cfg.param_dtype)) * 0.02)
        return p

    def _embed_in(params, batch):
        if "embeds" in batch:
            return batch["embeds"].astype(jnp.dtype(cfg.compute_dtype))
        return L.embed_tokens(cfg, params["embed"], batch["tokens"])

    def _unembed_out(params, x):
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        return L.unembed(cfg, w, x)

    def forward(params, batch, ac=_identity_ac, want_cache=False, remat=True,
                unroll=False):
        x = ac(_embed_in(params, batch), "hidden")
        B, S, _ = x.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        rope = make_rope(cfg, positions)

        def unit(x, unit_params):
            aux = jnp.float32(0)
            states = []
            for i in range(per_unit):
                if want_cache:
                    x, a, st = apply_tf_block_prefill(
                        cfg, unit_params[i], x, rope=rope, window=windows[i], ac=ac)
                    states.append(st)
                else:
                    x, a = apply_tf_block(cfg, unit_params[i], x,
                                          rope=rope, window=windows[i], ac=ac)
                aux = aux + a
            return x, aux, tuple(states)

        unit_fn = jax.checkpoint(unit) if (remat and not want_cache) else unit

        def body(carry, unit_params):
            x, aux = carry
            x, a, states = unit_fn(x, unit_params)
            return (x, aux + a), states

        carry = (x, jnp.float32(0))
        if n_dense:
            carry, lead = scan_blocks(body, carry, params["dense_blocks"], unroll)
        (x, aux), states = scan_blocks(body, carry, params["blocks"], unroll)
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = ac(_unembed_out(params, x), "logits")
        caches = None
        if want_cache:
            # tuple(per_unit) of state dicts, leaves [layers, B, S, ...]
            caches = states
            if n_dense:
                caches = (jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                       lead[0], states[0]),)
        return logits, aux / trip, caches

    def init_caches(batch_size, capacity):
        caps = [min(w, capacity) if w else capacity for w in windows]
        one = tuple(init_cache(cfg, batch_size, c) for c in caps)
        out = {"blocks": jax.tree.map(lambda a: jnp.broadcast_to(a, (trip,) + a.shape), one)}
        if n_dense:
            out["dense"] = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_dense,) + a.shape), one)
        if cfg.moe:
            out["routes"] = jnp.zeros((2,), jnp.int32)
        return out

    def caches_from_layers(layers):
        assert per_unit == 1
        out = {"blocks": (layers if not n_dense
                          else jax.tree.map(lambda a: a[n_dense:], layers),)}
        if n_dense:
            out["dense"] = (jax.tree.map(lambda a: a[:n_dense], layers),)
        if cfg.moe:
            out["routes"] = jnp.zeros((2,), jnp.int32)
        return out

    rope_fn = make_rope_fn(cfg)

    def decode_step(params, batch, caches, ac=_identity_ac, unroll=False):
        """The stacked caches ride the layer scan's carry, and each layer
        writes only the new token's rows into them: with ``caches`` donated,
        the step updates the cache where it lies."""
        x = L.embed_tokens(cfg, params["embed"], batch["tokens"])  # [B,1,D]
        pos = batch["pos"]

        def body(carry, scanned):
            x, n_local, stack = carry
            unit_params, layer = scanned
            new_stack = []
            for i in range(per_unit):
                x, c, n = apply_tf_block_decode(cfg, unit_params[i], x, stack[i], layer,
                                                pos, rope_fn=rope_fn, window=windows[i], ac=ac)
                new_stack.append(c)
                n_local = n_local + n
            return (x, n_local, tuple(new_stack)), None

        n_local, out = jnp.int32(0), {}
        for name, blocks, n in (("dense", "dense_blocks", n_dense), ("blocks", "blocks", trip)):
            if n:
                (x, n_local, out[name]), _ = scan_blocks(
                    body, (x, n_local, caches[name]),
                    (params[blocks], jnp.arange(n, dtype=jnp.int32)), unroll)
        if cfg.moe:
            n_routes = trip * per_unit * x.shape[0] * cfg.moe.top_k
            out["routes"] = caches["routes"] + jnp.stack([jnp.int32(n_routes), n_local])
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = ac(_unembed_out(params, x), "logits")
        return logits, out

    return Model(cfg, init, forward, decode_step, init_caches,
                 scan_info={"layer_trip": trip, "per_unit": per_unit},
                 caches_from_layers=caches_from_layers)


# ======================================================================
# RWKV6 (attention-free SSM)
# ======================================================================
def _build_rwkv(cfg: ArchConfig) -> Model:
    trip = cfg.n_layers

    def init(rng):
        k_e, k_b, k_u = jax.random.split(rng, 3)

        def init_block(k):
            return {
                "ln1": L.init_norm(cfg, cfg.d_model),
                "ln2": L.init_norm(cfg, cfg.d_model),
                "body": rwkv6.init_rwkv_block(cfg, k),
            }

        return {
            "embed": L.init_embedding(cfg, k_e),
            "ln_in": L.init_norm(cfg, cfg.d_model),
            "blocks": _stack_init(init_block, k_b, trip),
            "final_norm": L.init_norm(cfg, cfg.d_model),
            "unembed": (jax.random.normal(k_u, (cfg.d_model, cfg.vocab),
                                          jnp.dtype(cfg.param_dtype)) * 0.02),
        }

    def _block(x, bp, state, ac):
        norms = (partial(L.apply_norm, cfg, bp["ln1"]), partial(L.apply_norm, cfg, bp["ln2"]))

        def apply_norm_i(norm, h):
            return norm(h)

        x, new_state = rwkv6.apply_rwkv_block(
            cfg, bp["body"], x,
            norms=(bp["ln1"], bp["ln2"]),
            apply_norm=lambda np_, h: L.apply_norm(cfg, np_, h),
            state=state)
        return ac(x, "hidden"), new_state

    def forward(params, batch, ac=_identity_ac, want_cache=False, remat=True,
                unroll=False):
        x = L.embed_tokens(cfg, params["embed"], batch["tokens"])
        x = ac(L.apply_norm(cfg, params["ln_in"], x), "hidden")

        blk = jax.checkpoint(_block, static_argnums=(3,)) if remat and not want_cache else _block

        def body(x, bp):
            x, st = blk(x, bp, None, ac)
            return x, st if want_cache else None

        x, states = scan_blocks(body, x, params["blocks"], unroll)
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = ac(x @ params["unembed"].astype(x.dtype), "logits")
        return logits, jnp.float32(0), states

    def init_caches(batch_size, capacity):
        one = rwkv6.rwkv_state_init(cfg, batch_size)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (trip,) + a.shape), one)

    def decode_step(params, batch, states, ac=_identity_ac, unroll=False):
        x = L.embed_tokens(cfg, params["embed"], batch["tokens"])
        x = L.apply_norm(cfg, params["ln_in"], x)

        def body(x, scanned):
            bp, st = scanned
            x, new_st = _block(x, bp, st, ac)
            return x, new_st

        x, states = scan_blocks(body, x, (params["blocks"], states), unroll)
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = x @ params["unembed"].astype(x.dtype)
        return logits, states

    return Model(cfg, init, forward, decode_step, init_caches,
                 scan_info={"layer_trip": trip, "per_unit": 1, "time_scan": True})


# ======================================================================
# Zamba2 hybrid: mamba2 backbone + ONE weight-shared attention block
# ======================================================================
def _build_zamba(cfg: ArchConfig) -> Model:
    every = cfg.hybrid.attn_every
    assert cfg.n_layers % every == 0
    trip = cfg.n_layers // every     # superblocks: `every` mamba layers + shared attn

    def init(rng):
        k_e, k_b, k_a, k_m, k_u = jax.random.split(rng, 5)

        def init_super(k):
            ks = jax.random.split(k, every)
            blocks = tuple({"ln": L.init_norm(cfg, cfg.d_model),
                            "body": mamba2.init_mamba_block(cfg, ks[i])}
                           for i in range(every))
            return blocks

        return {
            "embed": L.init_embedding(cfg, k_e),
            "supers": _stack_init(init_super, k_b, trip),
            "shared_attn": {
                "ln1": L.init_norm(cfg, cfg.d_model),
                "attn": attn.init_attention(cfg, k_a),
                "ln2": L.init_norm(cfg, cfg.d_model),
                "mlp": L.init_mlp(cfg, k_m, cfg.d_model, cfg.d_ff),
            },
            "final_norm": L.init_norm(cfg, cfg.d_model),
            "unembed": (jax.random.normal(k_u, (cfg.d_model, cfg.vocab),
                                          jnp.dtype(cfg.param_dtype)) * 0.02),
        }

    def _super_fwd(x, sp, shared, rope, ac, want_cache, states):
        new_states = []
        for i in range(every):
            st = None if states is None else jax.tree.map(lambda a: a[i], states["mamba"])
            h = L.apply_norm(cfg, sp[i]["ln"], x)
            y, ns = mamba2.apply_mamba_block(cfg, sp[i]["body"], h, st)
            x = ac(x + y, "hidden")
            new_states.append(ns)
        # shared attention block (weights shared across all superblocks)
        h = L.apply_norm(cfg, shared["ln1"], x)
        if want_cache:
            out, kv = attn.attention_ctx(cfg, shared["attn"], h, rope=rope,
                                         causal=True, return_kv=True)
        else:
            out = attn.attention_ctx(cfg, shared["attn"], h, rope=rope, causal=True)
            kv = None
        x = ac(x + out, "hidden")
        h = L.apply_norm(cfg, shared["ln2"], x)
        x = ac(x + L.apply_mlp(cfg, shared["mlp"], h), "hidden")
        mamba_stack = jax.tree.map(lambda *a: jnp.stack(a), *new_states)
        return x, mamba_stack, kv

    def forward(params, batch, ac=_identity_ac, want_cache=False, remat=True,
                unroll=False):
        x = ac(L.embed_tokens(cfg, params["embed"], batch["tokens"]), "hidden")
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        rope = make_rope(cfg, positions)
        shared = params["shared_attn"]

        fwd = _super_fwd
        if remat and not want_cache:
            fwd = jax.checkpoint(_super_fwd, static_argnums=(4, 5))

        def body(x, sp):
            x, mstack, kv = fwd(x, sp, shared, rope, ac, want_cache, None)
            return x, (mstack, kv) if want_cache else None

        x, collected = scan_blocks(body, x, params["supers"], unroll)
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = ac(x @ params["unembed"].astype(x.dtype), "logits")
        return logits, jnp.float32(0), collected

    def init_caches(batch_size, capacity):
        m_one = mamba2.mamba_state_init(cfg, batch_size)
        mamba = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (trip, every) + a.shape), m_one)
        a_one = attn.init_attn_cache(cfg, batch_size, capacity)
        attn_c = jax.tree.map(lambda a: jnp.broadcast_to(a, (trip,) + a.shape), a_one)
        return {"mamba": mamba, "attn": attn_c}

    rope_fn = make_rope_fn(cfg)

    def decode_step(params, batch, caches, ac=_identity_ac, unroll=False):
        x = L.embed_tokens(cfg, params["embed"], batch["tokens"])
        pos = batch["pos"]
        shared = params["shared_attn"]

        def body(x, scanned):
            sp, mamba_st, attn_c = scanned
            new_m = []
            for i in range(every):
                st = jax.tree.map(lambda a: a[i], mamba_st)
                h = L.apply_norm(cfg, sp[i]["ln"], x)
                y, ns = mamba2.apply_mamba_block(cfg, sp[i]["body"], h, st)
                x = x + y
                new_m.append(ns)
            h = L.apply_norm(cfg, shared["ln1"], x)
            out, attn_c = attn.attention_decode(cfg, shared["attn"], h, attn_c, pos,
                                                rope_fn=rope_fn)
            x = x + out
            h = L.apply_norm(cfg, shared["ln2"], x)
            x = x + L.apply_mlp(cfg, shared["mlp"], h)
            m_stack = jax.tree.map(lambda *a: jnp.stack(a), *new_m)
            return x, (m_stack, attn_c)

        x, (mamba_new, attn_new) = scan_blocks(
            body, x, (params["supers"], caches["mamba"], caches["attn"]), unroll)
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = x @ params["unembed"].astype(x.dtype)
        return logits, {"mamba": mamba_new, "attn": attn_new}

    return Model(cfg, init, forward, decode_step, init_caches,
                 scan_info={"layer_trip": trip, "per_unit": every, "time_scan": True})


# ======================================================================
# Whisper (audio encoder-decoder, stubbed conv frontend)
# ======================================================================
def _build_whisper(cfg: ArchConfig) -> Model:
    enc_trip = cfg.encdec.n_encoder_layers
    dec_trip = cfg.n_layers
    # Learned decoder positions.  Whisper's real decoder caps at 448; the
    # assigned input shapes exercise the decoder structurally at up to 32k,
    # so the table is sized to cover them (noted in DESIGN.md §6).
    MAX_DEC_POS = 32768

    def init(rng):
        ks = jax.random.split(rng, 6)

        def init_enc_block(k):
            k1, k2 = jax.random.split(k)
            return {
                "ln1": L.init_norm(cfg, cfg.d_model),
                "attn": attn.init_attention(cfg, k1),
                "ln2": L.init_norm(cfg, cfg.d_model),
                "mlp": L.init_mlp(cfg, k2, cfg.d_model, cfg.d_ff),
            }

        def init_dec_block(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {
                "ln1": L.init_norm(cfg, cfg.d_model),
                "self_attn": attn.init_attention(cfg, k1),
                "ln_x": L.init_norm(cfg, cfg.d_model),
                "cross_attn": attn.init_attention(cfg, k2, cross=True),
                "ln2": L.init_norm(cfg, cfg.d_model),
                "mlp": L.init_mlp(cfg, k3, cfg.d_model, cfg.d_ff),
            }

        return {
            "enc_blocks": _stack_init(init_enc_block, ks[0], enc_trip),
            "enc_norm": L.init_norm(cfg, cfg.d_model),
            "embed": L.init_embedding(cfg, ks[1]),
            "dec_pos": (jax.random.normal(ks[2], (MAX_DEC_POS, cfg.d_model),
                                          jnp.dtype(cfg.param_dtype)) * 0.01),
            "dec_blocks": _stack_init(init_dec_block, ks[3], dec_trip),
            "dec_norm": L.init_norm(cfg, cfg.d_model),
        }

    def encode(params, feats, ac, unroll=False):
        cd = jnp.dtype(cfg.compute_dtype)
        x = feats.astype(cd)
        x = x + L.sinusoidal_embedding(x.shape[1], cfg.d_model).astype(cd)
        x = ac(x, "hidden")

        def body(x, bp):
            h = L.apply_norm(cfg, bp["ln1"], x)
            x = x + attn.attention_ctx(cfg, bp["attn"], h, rope=None, causal=False)
            h = L.apply_norm(cfg, bp["ln2"], x)
            return ac(x + L.apply_mlp(cfg, bp["mlp"], h), "hidden"), None

        x, _ = scan_blocks(body, x, params["enc_blocks"], unroll)
        return L.apply_norm(cfg, params["enc_norm"], x)

    def _dec_block(x, bp, enc_out, ac, want_cache):
        h = L.apply_norm(cfg, bp["ln1"], x)
        if want_cache:
            out, kv = attn.attention_ctx(cfg, bp["self_attn"], h, rope=None,
                                         causal=True, return_kv=True)
        else:
            out = attn.attention_ctx(cfg, bp["self_attn"], h, rope=None, causal=True)
            kv = None
        x = x + out
        h = L.apply_norm(cfg, bp["ln_x"], x)
        if want_cache:
            out, cross_kv = attn.attention_ctx(cfg, bp["cross_attn"], h, rope=None,
                                               causal=False, kv_x=enc_out, return_kv=True)
        else:
            out = attn.attention_ctx(cfg, bp["cross_attn"], h, rope=None,
                                     causal=False, kv_x=enc_out)
            cross_kv = None
        x = ac(x + out, "hidden")
        h = L.apply_norm(cfg, bp["ln2"], x)
        x = ac(x + L.apply_mlp(cfg, bp["mlp"], h), "hidden")
        return x, kv, cross_kv

    def forward(params, batch, ac=_identity_ac, want_cache=False, remat=True,
                unroll=False):
        enc_out = encode(params, batch["encoder_feats"], ac, unroll)
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = L.embed_tokens(cfg, params["embed"], tokens)
        x = x + params["dec_pos"][:S].astype(x.dtype)
        x = ac(x, "hidden")

        blk = _dec_block
        if remat and not want_cache:
            blk = jax.checkpoint(_dec_block, static_argnums=(3, 4))

        def body(x, bp):
            x, kv, cross_kv = blk(x, bp, enc_out, ac, want_cache)
            return x, (kv, cross_kv) if want_cache else None

        x, caches = scan_blocks(body, x, params["dec_blocks"], unroll)
        x = L.apply_norm(cfg, params["dec_norm"], x)
        logits = ac(x @ params["embed"].T.astype(x.dtype), "logits")
        return logits, jnp.float32(0), caches

    def init_caches(batch_size, capacity):
        self_c = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (dec_trip,) + a.shape),
            attn.init_attn_cache(cfg, batch_size, capacity))
        cd = jnp.dtype(cfg.compute_dtype)
        KV, hd = cfg.n_kv_heads, cfg.head_dim
        enc_s = cfg.encdec.encoder_seq
        cross = {
            "k": jnp.zeros((dec_trip, batch_size, enc_s, KV, hd), cd),
            "v": jnp.zeros((dec_trip, batch_size, enc_s, KV, hd), cd),
        }
        return {"self": self_c, "cross": cross}

    def decode_step(params, batch, caches, ac=_identity_ac, unroll=False):
        tokens = batch["tokens"]
        pos = batch["pos"]
        B = tokens.shape[0]
        x = L.embed_tokens(cfg, params["embed"], tokens)
        x = x + jnp.take(params["dec_pos"],
                         jnp.minimum(pos, MAX_DEC_POS - 1)[None], axis=0)[None].astype(x.dtype)

        def body(x, scanned):
            bp, self_c, cross_k, cross_v = scanned
            h = L.apply_norm(cfg, bp["ln1"], x)
            out, self_c = attn.attention_decode(cfg, bp["self_attn"], h, self_c, pos)
            x = x + out
            # cross attention against precomputed encoder K/V
            h = L.apply_norm(cfg, bp["ln_x"], x)
            H, KVh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
            cd = x.dtype
            q = (h @ bp["cross_attn"]["wq"].astype(cd))
            if cfg.qkv_bias:
                q = q + bp["cross_attn"]["bq"].astype(cd)
            q = q.reshape(B, 1, KVh, H // KVh, hd)
            import numpy as _np
            scores = jnp.einsum("bckgd,bskd->bkgcs", q, cross_k) / _np.sqrt(hd)
            w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cd)
            out = jnp.einsum("bkgcs,bskd->bckgd", w, cross_v)
            out = out.reshape(B, 1, H * hd) @ bp["cross_attn"]["wo"].astype(cd)
            x = x + out
            h = L.apply_norm(cfg, bp["ln2"], x)
            x = x + L.apply_mlp(cfg, bp["mlp"], h)
            return x, self_c

        x, self_new = scan_blocks(
            body, x, (params["dec_blocks"], caches["self"],
                      caches["cross"]["k"], caches["cross"]["v"]), unroll)
        x = L.apply_norm(cfg, params["dec_norm"], x)
        logits = x @ params["embed"].T.astype(x.dtype)
        return logits, {"self": self_new, "cross": caches["cross"]}

    return Model(cfg, init, forward, decode_step, init_caches,
                 scan_info={"layer_trip": dec_trip, "per_unit": 1,
                            "enc_trip": enc_trip})
