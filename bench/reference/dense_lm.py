"""Plain float32 reference of a dense decoder-only LM (pre-norm RMSNorm,
rotary attention with grouped K/V heads and optional q/k/v biases, gated
SiLU MLP, optional tied output head), and the seeded weights that both the
reference and the system under test are given.

It imports nothing of the program.  The parameter tree is laid out as the
serving engine reads it: per-layer weights stacked on a leading layer axis
under ``blocks[0]``.  Every matmul runs at ``precision="highest"``, so on a
TPU the float32 products are not rounded to bfloat16.

``precision="fp8"`` is the control: the same forward with both operands of
every matmul rounded to float8 e4m3 with a per-tensor scale (the largest
magnitude maps to 448), products accumulated in float32.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

FP8_MAX = 448.0


def seed_key(seed: int, stream: int) -> jax.Array:
    """A threefry key from any whole-number seed (wider than 32 bits too)
    and a stream number, so weights and inputs draw independent bits."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="threefry2x32")


def _shapes(c: dict) -> dict:
    L, d, f, v = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    attn = {"wq": (L, d, h * hd), "wk": (L, d, kv * hd), "wv": (L, d, kv * hd),
            "wo": (L, h * hd, d)}
    if c["qkv_bias"]:
        attn.update(bq=(L, h * hd), bk=(L, kv * hd), bv=(L, kv * hd))
    tree = {
        "embed": (v, d),
        "blocks": ({"ln1": {"scale": (L, d)}, "attn": attn, "ln2": {"scale": (L, d)},
                    "mlp": {"wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d)}},),
        "final_norm": {"scale": (d,)},
    }
    if not c["tie_word_embeddings"]:
        tree["unembed"] = (d, v)
    return tree


def _init_leaf(c: dict, path: str, key, shape):
    d, f = c["hidden_size"], c["intermediate_size"]
    hq = c["num_attention_heads"] * c["head_dim"]
    name = path.rsplit("/", 1)[-1]
    if name == "scale":                       # norm gains near 1
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    std = {"embed": 0.02, "unembed": 0.02, "wo": hq ** -0.5, "wd": f ** -0.5,
           "bq": 0.02, "bk": 0.02, "bv": 0.02}.get(name, d ** -0.5)
    return std * jax.random.normal(key, shape, jnp.float32)


def make_params(c: dict, seed: int):
    """Every weight, float32, on the default device, in one jitted call."""
    shapes = _shapes(c)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, int) for i in x))
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in leaves]

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        return [_init_leaf(c, path, keys[i], shape)
                for i, (path, (_, shape)) in enumerate(zip(paths, leaves))]

    return jax.tree_util.tree_unflatten(treedef, init(seed_key(seed, 0)))


# ------------------------------------------------------------------ forward
def _q8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    if fp8:
        a, w = _q8(a), _q8(w)
    return jnp.matmul(a, w, precision="highest")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, T, heads, hd] at positions 0..T-1; rotates the two halves."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(c: dict, fp8: bool, x, p):
    B, T, _ = x.shape
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    a = p["attn"]
    y = _rms(x, p["ln1"]["scale"], eps)
    q, k, v = _mm(y, a["wq"], fp8), _mm(y, a["wk"], fp8), _mm(y, a["wv"], fp8)
    if c["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(B, T, h, hd), c["rope_theta"])
    k = _rope(k.reshape(B, T, kv, hd), c["rope_theta"])
    v = v.reshape(B, T, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)           # query head i reads K/V head i // (h/kv)
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision="highest").reshape(B, T, h * hd)
    x = x + _mm(o, a["wo"], fp8)
    y = _rms(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    x = x + _mm(jax.nn.silu(_mm(y, m["wg"], fp8)) * _mm(y, m["wu"], fp8), m["wd"], fp8)
    return x, None


@partial(jax.jit, static_argnames=("c_items", "first", "fp8"))
def _logits(params, tokens, *, c_items, first: int, fp8: bool):
    c = dict(c_items)
    x = jnp.take(params["embed"], tokens, axis=0)
    x, _ = jax.lax.scan(partial(_layer, c, fp8), x, params["blocks"][0])
    x = _rms(x[:, first:], params["final_norm"]["scale"], c["rms_norm_eps"])
    head = params["embed"].T if c["tie_word_embeddings"] else params["unembed"]
    return _mm(x, head, fp8)


def logits(c: dict, params, tokens: np.ndarray, first: int, *, fp8: bool = False):
    """Float32 logits [B, T - first, vocab] at positions first..T-1 of
    ``tokens`` [B, T]; position t predicts token t + 1."""
    items = tuple(sorted((k, v) for k, v in c.items() if isinstance(v, (int, float, bool, str))))
    return _logits(params, jnp.asarray(tokens, jnp.int32), c_items=items, first=first, fp8=fp8)


@jax.jit
def _gaps(ref, picked):
    """How far the logit of each picked token lies below the reference's
    best at the same position."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, picked[..., None], axis=-1)[..., 0]
    return best - got


def served_gaps(c: dict, params, prompts: np.ndarray, served: np.ndarray,
                *, block: int = 4, control: bool = False) -> np.ndarray:
    """Gaps [n, new] of ``served`` tokens [n, new] after ``prompts`` [n, S].

    Without ``control``: the gap of each served token.  With it: the gap of
    the token that the fp8 forward puts first at each of those positions.
    Computed ``block`` requests at a time so that it fits beside nothing
    else on the chip."""
    n, S = prompts.shape
    out = []
    for i in range(0, n, block):
        seq = np.concatenate([prompts[i:i + block], served[i:i + block, :-1]], axis=1)
        ref = logits(c, params, seq, S - 1)
        if control:
            picked = jnp.argmax(logits(c, params, seq, S - 1, fp8=True), axis=-1)
        else:
            picked = jnp.asarray(served[i:i + block], jnp.int32)
        out.append(np.asarray(_gaps(ref, picked)))
    return np.concatenate(out)
