"""Moonlight's mechanisms against the plain float32 reference
(``bench/reference/mla_moe_lm.py``) at a small size on the CPU: latent
attention in prefill and in absorbed decode through the latent cache, the
dropless expert layer, the selection bias, one device's share of
expert-parallel experts, and the host-KV tier holding the latent."""
import dataclasses
import importlib.util
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import build_model, moe as moe_mod
from repro.models.attention import prefill_cache
from repro.serve.counters import counters
from repro.serve.engine import ServeEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _load(rel: str):
    name = "bench_" + rel.replace("/", "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, BENCH / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference/mla_moe_lm.py")
DRIVER = _load("drivers/serve_mla_moe.py")

# Moonlight's keys at a small size: 1 dense + 2 MoE layers, a router over 16
# experts of which this device holds 4 (from expert 4 on), top-6, 2 shared.
SMALL = {
    "name": "moonlight-small", "source": "test", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 96, "moe_intermediate_size": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "router_experts": 16, "n_routed_experts": 4, "first_held_expert": 4,
    "num_experts_per_tok": 6, "n_shared_experts": 2, "scoring_func": "sigmoid",
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5, "rope_theta": 50000,
    "vocab_size": 256, "tie_word_embeddings": False,
    "param_dtype": "bfloat16", "compute_dtype": "float32",
}


def _model(c=SMALL):
    return build_model(DRIVER.arch_config(c))


def _tokens(B, S, seed=0):
    return np.random.default_rng(seed).integers(0, SMALL["vocab_size"], (B, S)).astype(np.int32)


def test_mla_prefill_logits_match_reference():
    model, params = _model(), REF.make_params(SMALL, 1)
    toks = _tokens(2, 24)
    got, _, _ = model.forward(params, {"tokens": jnp.asarray(toks)}, remat=False)
    want = REF.logits(SMALL, params, toks, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_absorbed_decode_through_latent_cache_matches_reference():
    """Prefill 12 tokens, build the latent cache the engine builds, then
    decode 8 more with W_uk/W_uv absorbed: every decode step's logits are
    the reference's full forward at that position."""
    model, params = _model(), REF.make_params(SMALL, 2)
    toks = _tokens(2, 20, seed=1)
    S = 12
    _, _, (state,) = model.forward(params, {"tokens": jnp.asarray(toks[:, :S])},
                                   want_cache=True, remat=False)
    assert set(state) == {"c", "k_pe"}
    caches = model.caches_from_layers(jax.vmap(
        lambda st: prefill_cache(model.cfg, st, 24))(state))
    want = np.asarray(REF.logits(SMALL, params, toks, S))
    for t in range(S, toks.shape[1]):
        got, caches = model.decode_step(
            params, {"tokens": jnp.asarray(toks[:, t:t + 1]), "pos": jnp.int32(t)}, caches)
        np.testing.assert_allclose(np.asarray(got[:, 0]), want[:, t - S], rtol=2e-4, atol=2e-5)


def _moe_params(cfg, seed):
    p = moe_mod.init_moe(cfg, jax.random.PRNGKey(seed))
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    p["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), p["bias"].shape)
    return p


def _dense_moe(cfg, p, x):
    """Every held expert on every token, weighted by the route weights:
    the layer with nothing dropped, written plainly."""
    m = cfg.moe
    first, n = m.held_range
    xf = x.reshape(-1, x.shape[-1])
    w, e, _ = moe_mod.route(cfg, p, xf)
    out = jnp.zeros_like(xf)
    for j in range(n):
        gate = jnp.sum(jnp.where(e == first + j, w, 0.0), axis=-1)
        h = jax.nn.silu(xf @ p["wg"][j]) * (xf @ p["wu"][j])
        out = out + gate[:, None] * (h @ p["wd"][j])
    sh = p["shared"]
    out = out + (jax.nn.silu(xf @ sh["wg"]) * (xf @ sh["wu"])) @ sh["wd"]
    return out.reshape(x.shape)


def test_dropless_layer_keeps_every_token_under_skew():
    """A router skewed so that one expert takes every token: the dropless
    layer computes all of them, where a capacity of 1.25 drops most."""
    cfg = dataclasses.replace(DRIVER.arch_config(SMALL), compute_dtype="float32")
    p = _moe_params(cfg, 3)
    first = cfg.moe.held_range[0]
    p["bias"] = p["bias"].at[first].set(100.0)          # every token picks it
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, SMALL["hidden_size"]))
    _, e, _ = moe_mod.route(cfg, p, x.reshape(64, -1))
    assert bool(jnp.all(jnp.any(e == first, axis=-1)))
    got, _, n_local = moe_mod.apply_moe(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense_moe(cfg, p, x)),
                               rtol=1e-5, atol=1e-5)
    assert int(n_local) == int(jnp.sum((e >= first) & (e < first + cfg.moe.held_range[1])))
    capped = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.25))
    dropped, _, _ = moe_mod.apply_moe(capped, p, x)
    assert not np.allclose(np.asarray(dropped), np.asarray(got), atol=1e-3)


@pytest.mark.parametrize("skewed", [False, True], ids=["plain", "skewed"])
@pytest.mark.parametrize("n_tokens", [32, 256], ids=["decode", "prefill"])
def test_dropless_forms_match_the_per_expert_loop(n_tokens, skewed, monkeypatch):
    """At a decode-size call (dense form) and a prefill-size one above the
    bound (grouped form), with the plain router and with one that sends
    every token to one held expert: the form the bound picks, and the other
    one forced on the same tokens, each give the per-expert loop's output
    and the same routes to held experts."""
    cfg = dataclasses.replace(DRIVER.arch_config(SMALL), compute_dtype="float32")
    assert moe_mod.held_dense(cfg, n_tokens) == (n_tokens == 32)
    p = _moe_params(cfg, 11)
    first, held = cfg.moe.held_range
    if skewed:
        p["bias"] = p["bias"].at[first].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, n_tokens // 2, SMALL["hidden_size"]))
    _, e, _ = moe_mod.route(cfg, p, x.reshape(n_tokens, -1))
    want = np.asarray(_dense_moe(cfg, p, x))
    n_held = int(jnp.sum((e >= first) & (e < first + held)))
    got, _, n_local = moe_mod.apply_moe(cfg, p, x)
    monkeypatch.setattr(moe_mod, "HELD_DENSE_TOKENS", 0 if n_tokens == 32 else n_tokens)
    assert moe_mod.held_dense(cfg, n_tokens) == (n_tokens != 32)
    other, _, n_local_other = moe_mod.apply_moe(cfg, p, x)
    for out in (got, other):
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    assert int(n_local) == int(n_local_other) == n_held > 0


@pytest.mark.parametrize("n_tokens", [32, 256], ids=["decode", "prefill"])
def test_an_unrouted_expert_that_overflows_adds_nothing(n_tokens):
    """A held expert no token picks, with weights that overflow bf16 on
    every row: in either form its rows stay out of the output, which is
    finite and what the layer gives with that expert's weights zeroed."""
    cfg = DRIVER.arch_config(dict(SMALL, compute_dtype="bfloat16"))
    p = moe_mod.init_moe(cfg, jax.random.PRNGKey(13))
    first = cfg.moe.held_range[0]
    p["bias"] = p["bias"].at[first].set(-100.0)          # no token picks it
    x = jax.random.normal(jax.random.PRNGKey(14), (2, n_tokens // 2, SMALL["hidden_size"]),
                          jnp.bfloat16)
    _, e, _ = moe_mod.route(cfg, p, x.reshape(n_tokens, -1))
    assert not bool(jnp.any(e == first))
    huge = dict(p, wg=p["wg"].at[0].set(1e30), wu=p["wu"].at[0].set(1e30))
    zero = dict(p, **{k: p[k].at[0].set(0) for k in ("wg", "wu", "wd")})
    got, _, _ = moe_mod.apply_moe(cfg, huge, x)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(moe_mod.apply_moe(cfg, zero, x)[0], np.float32))


def test_selection_bias_moves_the_choice_not_the_weights():
    cfg = DRIVER.arch_config(SMALL)
    p = _moe_params(cfg, 5)
    xf = jax.random.normal(jax.random.PRNGKey(6), (64, SMALL["hidden_size"]))
    s = jax.nn.sigmoid(xf @ p["router"])
    K, scale = cfg.moe.top_k, cfg.moe.routed_scaling
    w0, e0, _ = moe_mod.route(cfg, dict(p, bias=jnp.zeros_like(p["bias"])), xf)
    w1, e1, _ = moe_mod.route(cfg, p, xf)
    assert not np.array_equal(np.sort(np.asarray(e0)), np.sort(np.asarray(e1)))
    for w, e, bias in ((w0, e0, 0.0), (w1, e1, p["bias"])):
        np.testing.assert_array_equal(
            np.sort(np.asarray(e)), np.sort(np.asarray(jax.lax.top_k(s + bias, K)[1])))
        chosen = jnp.take_along_axis(s, e, axis=-1)
        np.testing.assert_allclose(np.asarray(w), np.asarray(chosen / chosen.sum(-1,
                                   keepdims=True) * scale), rtol=1e-6)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight devices each hold 2 of the 16 experts: their outputs,
    with the shared experts (which every device computes alike) counted
    once, add up to the layer that holds them all."""
    whole = dataclasses.replace(DRIVER.arch_config(SMALL), compute_dtype="float32")
    whole = dataclasses.replace(whole, moe=dataclasses.replace(whole.moe, held=None))
    p = _moe_params(whole, 7)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, SMALL["hidden_size"]))
    want, _, _ = moe_mod.apply_moe(whole, p, x)
    sh = p["shared"]
    shared = (jax.nn.silu(x @ sh["wg"]) * (x @ sh["wu"])) @ sh["wd"]
    total, routes = -7 * shared, 0
    for d in range(8):
        part = dataclasses.replace(whole, moe=dataclasses.replace(whole.moe, held=(2 * d, 2)))
        pd = dict(p, **{k: p[k][2 * d:2 * d + 2] for k in ("wg", "wu", "wd")})
        y, _, n_local = moe_mod.apply_moe(part, pd, x)
        total, routes = total + y, routes + int(n_local)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert routes == x.shape[0] * x.shape[1] * whole.moe.top_k


def test_host_kv_hit_serves_the_miss_tokens_and_stores_the_latent():
    """A hit serves the tokens of the miss that saved its context; the store
    holds L x (latent + rotated key) x 2 bytes a token, and the fetch moves
    exactly that; decode counts every route and those to held experts."""
    c = dict(SMALL, compute_dtype="bfloat16")
    model, params = _model(c), REF.make_params(c, 9)
    eng = ServeEngine(model, params)
    prompts = _tokens(2, 32, seed=2)
    keys = [f"mla-{i}" for i in range(2)]
    c0 = counters()
    miss = eng.generate(prompts, keys, 6)
    c1 = counters()
    hit = eng.generate(prompts, keys, 6)
    c2 = counters()
    assert hit.request_stats[0].cache_hit and not miss.request_stats[0].cache_hit
    np.testing.assert_array_equal(hit.tokens, miss.tokens)
    per_token = c["num_hidden_layers"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * 2
    for key in keys:
        saved = eng.store.saved(key)
        assert set(saved) == {"c", "k_pe"}
        assert sum(a.nbytes for a in saved.values()) == per_token * 32
    fetched = c2["kv.fetch.to_device_bytes"] - c1.get("kv.fetch.to_device_bytes", 0)
    assert fetched / (c2["kv.fetch.tokens"] - c1.get("kv.fetch.tokens", 0)) == per_token
    routes = c1["moe.routes"] - c0.get("moe.routes", 0)
    local = c1["moe.routes.local"] - c0.get("moe.routes.local", 0)
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    assert routes == 5 * n_moe * 2 * c["num_experts_per_tok"]        # 5 decode steps
    assert 0 < local < routes
    assert c1["moe.held_dense.steps"] - c0.get("moe.held_dense.steps", 0) == 5
    assert c2["moe.routes"] - c1["moe.routes"] == routes + n_moe * 2 * c["num_experts_per_tok"]


def test_published_latent_is_31104_bytes_a_token():
    """At Moonlight's published widths a context's cache state is the latent
    and the rotated key, 27 x (512 + 64) x 2 B = 31,104 B a token."""
    cfg = get_config("moonlight-16b-a3b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    _, _, (state,) = jax.eval_shape(
        lambda p, t: model.forward(p, {"tokens": t}, want_cache=True, remat=False),
        params, jax.ShapeDtypeStruct((1, 16), jnp.int32))
    per_token = sum(np.prod(a.shape) * a.dtype.itemsize for a in state.values()) // 16
    assert per_token == 31104
