"""Hierarchical shard_map MoE dispatch vs a no-drop dense oracle."""
import pytest

LATTE_MOE_TEST = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.core.latte_moe import make_latte_moe
from repro.models import moe as moe_mod

N = 8
mesh = make_mesh((N,), ("x",))

cfg = get_config("mixtral-8x7b").reduced()       # 4 experts top-2 reduced
cfg = dataclasses.replace(
    cfg, d_model=64,
    moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=2, d_ff_expert=32,
                            capacity_factor=64.0))   # no drops
rng = jax.random.PRNGKey(0)
p = moe_mod.init_moe(cfg, rng)
B, S, D = 8, 16, cfg.d_model
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)

# dense no-drop oracle: per-token weighted mix of expert FFNs
def oracle(p, x):
    T = B * S
    xf = x.reshape(T, D)
    logits = xf @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    tp, te = jax.lax.top_k(probs, cfg.moe.top_k)
    tp = tp / tp.sum(-1, keepdims=True)
    h = jnp.einsum("td,edf->tef", xf, p["wg"])
    u = jnp.einsum("td,edf->tef", xf, p["wu"])
    y_all = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, p["wd"])   # [T,E,D]
    w = jnp.zeros((T, cfg.moe.n_experts)).at[jnp.arange(T)[:, None], te].add(tp)
    return jnp.einsum("te,ted->td", w, y_all).reshape(B, S, D)

ref = oracle(p, x)
fn = make_latte_moe(cfg, mesh, "x")
out, aux = jax.jit(fn)(p, x)
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-4, err
assert np.isfinite(float(aux))

# verify the collective actually present: pairwise all-to-all appears in HLO
txt = jax.jit(fn).lower(p, x).compile().as_text()
assert "collective-permute" in txt or "all-to-all" in txt
print("LATTE_MOE_OK err=", err)
"""


@pytest.mark.slow
def test_latte_moe_matches_dense_oracle(subproc):
    out = subproc(LATTE_MOE_TEST, n_devices=8, timeout=600)
    assert "LATTE_MOE_OK" in out
