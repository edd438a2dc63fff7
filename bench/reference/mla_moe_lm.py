"""Plain float32 reference of a DeepSeek-V3-style decoder (Moonlight):
multi-head latent attention without a query LoRA, leading dense layers,
then MoE layers with sigmoid routing, a selection bias, normalised and
scaled top-k weights and shared experts; and the seeded weights that both
the reference and the system under test are given.

It follows the deepseek_v3 modelling code and imports nothing of the
program.  Per layer, with x pre-normed (RMSNorm):

    q = x Wq -> per head q_nope, q_pe;  [c, k_pe] = x Wkva;  c = RMSNorm(c)
    [k_nope, v] = c Wkvb per head;  RoPE on q_pe, k_pe in pairs (2i, 2i+1)
    scores over [k_nope, k_pe] / sqrt(nope + rope), causal softmax, o Wo
    s = sigmoid(x Wr);  top-k of s + bias;  weights s / sum * scaling
    out = sum over chosen held experts of weight * SwiGLU_e(x) + shared(x)

The configuration is one chip's share of expert parallelism: the router
keeps its published width (``router_experts``), the chip holds
``n_routed_experts`` experts from ``first_held_expert`` on, and routes to
the others add nothing, as in the program.  The decompressed attention is
the reference; the program's decode attends over the latent (absorbed).

The parameter tree is laid out as the program reads it, weights stored in
bfloat16 (the published dtype) and cast to float32 layer by layer inside
the scan, every matmul at ``precision="highest"``.  ``fp8=True`` is the
control: every matmul's operands rounded to float8 e4m3 with a per-tensor
scale, as ``dense_lm`` does.
"""
from __future__ import annotations

import importlib.util
from functools import partial
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp


def _sibling(name: str):
    """The reference module ``name`` beside this file (seeds and fp8)."""
    spec = importlib.util.spec_from_file_location(f"_mla_moe_lm_{name}",
                                                  Path(__file__).with_name(f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_dense = _sibling("dense_lm")
seed_key, _mm, _rms, _gaps = _dense.seed_key, _dense._mm, _dense._rms, _dense._gaps


def _attn_shapes(c: dict, L: int) -> dict:
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return {"wq": (L, d, h * (nope + rope)), "wkv_a": (L, d, r + rope),
            "kv_norm": {"scale": (L, r)}, "wkv_b": (L, r, h * (nope + v)),
            "wo": (L, h * v, d)}


def _shapes(c: dict) -> dict:
    d, V = c["hidden_size"], c["vocab_size"]
    Ld = c["first_k_dense_replace"]
    Lm = c["num_hidden_layers"] - Ld
    f, fe, E = c["intermediate_size"], c["moe_intermediate_size"], c["router_experts"]
    n, fs = c["n_routed_experts"], c["n_shared_experts"] * c["moe_intermediate_size"]
    dense = {"ln1": {"scale": (Ld, d)}, "attn": _attn_shapes(c, Ld), "ln2": {"scale": (Ld, d)},
             "mlp": {"wg": (Ld, d, f), "wu": (Ld, d, f), "wd": (Ld, f, d)}}
    moe = {"router": (Lm, d, E), "bias": (Lm, E),
           "wg": (Lm, n, d, fe), "wu": (Lm, n, d, fe), "wd": (Lm, n, fe, d),
           "shared": {"wg": (Lm, d, fs), "wu": (Lm, d, fs), "wd": (Lm, fs, d)}}
    return {"embed": (V, d), "dense_blocks": (dense,),
            "blocks": ({"ln1": {"scale": (Lm, d)}, "attn": _attn_shapes(c, Lm),
                        "ln2": {"scale": (Lm, d)}, "moe": moe},),
            "final_norm": {"scale": (d,)}, "unembed": (d, V)}


def _init_leaf(path: str, key, shape):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":                        # norm gains near 1
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "bias":                         # the selection bias: moves choices
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    std = 0.02 if name in ("embed", "unembed") else shape[-2] ** -0.5   # 1/sqrt(fan-in)
    return std * jax.random.normal(key, shape, jnp.float32)


def make_params(c: dict, seed: int):
    """Every weight, rounded to bfloat16, on the default device, in one
    jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(c), is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, int) for i in x))
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in leaves]

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        return [_init_leaf(path, keys[i], shape).astype(jnp.bfloat16)
                for i, (path, (_, shape)) in enumerate(zip(paths, leaves))]

    return jax.tree_util.tree_unflatten(treedef, init(seed_key(seed, 0)))


# ------------------------------------------------------------------ forward
def _rope_pairs(x, theta):
    """x [B, T, heads, d] at positions 0..T-1: de-interleave the pairs
    (2i, 2i+1) into halves, then rotate the halves by p * theta^(-2i/d)."""
    T, d = x.shape[1], x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(c: dict, fp8: bool, y, a):
    B, T, _ = y.shape
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    q = _mm(y, a["wq"], fp8).reshape(B, T, h, nope + rope)
    kv = _mm(y, a["wkv_a"], fp8)
    ckv = _rms(kv[..., :r], a["kv_norm"]["scale"], c["rms_norm_eps"])
    k_pe = _rope_pairs(kv[..., None, r:], c["rope_theta"])
    kvb = _mm(ckv, a["wkv_b"], fp8).reshape(B, T, h, -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_pe, (B, T, h, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], c["rope_theta"])], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, kvb[..., nope:], precision="highest")
    return _mm(o.reshape(B, T, -1), a["wo"], fp8)


def _mlp(fp8, y, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(y, wg, fp8)) * _mm(y, wu, fp8), wd, fp8)


def _moe(c: dict, fp8: bool, y, m):
    s = jax.nn.sigmoid(_mm(y, m["router"], fp8))                 # [B, T, router_experts]
    _, idx = jax.lax.top_k(s + m["bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
    first = c["first_held_expert"]

    def expert(acc, j):
        gate = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)   # [B, T]
        out = _mlp(fp8, y, m["wg"][j], m["wu"][j], m["wd"][j])
        return acc + gate[..., None] * out, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(y), jnp.arange(c["n_routed_experts"]))
    sh = m["shared"]
    return routed + _mlp(fp8, y, sh["wg"], sh["wu"], sh["wd"])


def _layer(c: dict, fp8: bool, x, p):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = c["rms_norm_eps"]
    x = x + _attention(c, fp8, _rms(x, p["ln1"]["scale"], eps), p["attn"])
    y = _rms(x, p["ln2"]["scale"], eps)
    if "mlp" in p:
        return x + _mlp(fp8, y, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"]), None
    return x + _moe(c, fp8, y, p["moe"]), None


@partial(jax.jit, static_argnames=("c_items", "first", "fp8"))
def _logits(params, tokens, *, c_items, first: int, fp8: bool):
    c = dict(c_items)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(partial(_layer, c, fp8), x, params["dense_blocks"][0])
    x, _ = jax.lax.scan(partial(_layer, c, fp8), x, params["blocks"][0])
    x = _rms(x[:, first:], params["final_norm"]["scale"].astype(jnp.float32),
             c["rms_norm_eps"])
    return _mm(x, params["unembed"].astype(jnp.float32), fp8)


def logits(c: dict, params, tokens: np.ndarray, first: int, *, fp8: bool = False):
    """Float32 logits [B, T - first, vocab] at positions first..T-1 of
    ``tokens`` [B, T]; position t predicts token t + 1."""
    items = tuple(sorted((k, v) for k, v in c.items() if isinstance(v, (int, float, bool, str))))
    return _logits(params, jnp.asarray(tokens, jnp.int32), c_items=items, first=first, fp8=fp8)


def served_gaps(c: dict, params, prompts: np.ndarray, served: np.ndarray,
                *, block: int = 4, control: bool = False, shift: int = 0):
    """Gaps [n, new] of ``served`` tokens [n, new] after ``prompts`` [n, S]:
    how far each one's reference logit lies below the reference's best.
    With ``control``: of the token the fp8 forward puts first instead.
    With ``shift``: also, from the same forward, the gap [n] each request's
    last served token would read shifted by ``shift`` in the vocabulary,
    its context kept (one altered token a request), as a second array.
    Computed ``block`` requests at a time."""
    n, S = prompts.shape
    out, altered = [], []
    for i in range(0, n, block):
        seq = np.concatenate([prompts[i:i + block], served[i:i + block, :-1]], axis=1)
        ref = logits(c, params, seq, S - 1)
        if control:
            picked = jnp.argmax(logits(c, params, seq, S - 1, fp8=True), axis=-1)
        else:
            picked = jnp.asarray(served[i:i + block], jnp.int32)
        out.append(np.asarray(_gaps(ref, picked)))
        if shift:
            last = (picked[:, -1] + shift) % c["vocab_size"]
            altered.append(np.asarray(_gaps(ref[:, -1], last)))
    if shift:
        return np.concatenate(out), np.concatenate(altered)
    return np.concatenate(out)
