"""Per-Pallas-kernel validation: shape/dtype sweeps against the pure-jnp
oracles (interpret mode); the distributed remote-DMA kernels run in a
subprocess with 8 emulated devices."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import paged_decode_attention_ref
from repro.kernels.paged_kv_gather.ops import gather_blocks
from repro.kernels.paged_kv_gather.ref import paged_kv_gather_ref


class TestPagedKVGather:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n_pool,bt,dkv,n_blocks", [
        (32, 16, 128, 8),
        (64, 16, 256, 17),
        (8, 8, 512, 8),
        (128, 32, 128, 1),
    ])
    def test_matches_oracle(self, dtype, n_pool, bt, dkv, n_blocks):
        rng = jax.random.PRNGKey(n_pool + n_blocks)
        pool = jax.random.normal(rng, (n_pool, bt, dkv)).astype(dtype)
        tbl = jax.random.permutation(rng, n_pool)[:n_blocks].astype(jnp.int32)
        out = gather_blocks(pool, tbl, interpret=True)
        ref = paged_kv_gather_ref(pool, tbl)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_repeated_blocks(self):
        pool = jnp.arange(16 * 8 * 128, dtype=jnp.float32).reshape(16, 8, 128)
        tbl = jnp.array([3, 3, 0, 15], jnp.int32)
        out = gather_blocks(pool, tbl, interpret=True)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))
        np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(pool[15]))


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("B,KV,G,hd,bt,mb", [
        (2, 2, 4, 128, 16, 4),
        (1, 1, 8, 128, 16, 2),
        (4, 4, 2, 256, 8, 3),
    ])
    def test_matches_oracle(self, dtype, tol, B, KV, G, hd, bt, mb):
        ks = jax.random.split(jax.random.PRNGKey(B * 31 + mb), 4)
        npool = mb * B + 2
        q = jax.random.normal(ks[0], (B, KV, G, hd)).astype(dtype)
        kp = jax.random.normal(ks[1], (npool, KV, bt, hd)).astype(dtype)
        vp = jax.random.normal(ks[2], (npool, KV, bt, hd)).astype(dtype)
        tables = jax.random.randint(ks[3], (B, mb), 0, npool)
        lengths = jnp.asarray(np.random.default_rng(0).integers(1, mb * bt, B),
                              jnp.int32)
        out = decode_attention(q, kp, vp, tables, lengths, interpret=True)
        ref = paged_decode_attention_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=tol, rtol=tol)

    def test_softcap(self):
        ks = jax.random.split(jax.random.PRNGKey(9), 4)
        q = jax.random.normal(ks[0], (2, 2, 4, 128))
        kp = jax.random.normal(ks[1], (8, 2, 16, 128))
        vp = jax.random.normal(ks[2], (8, 2, 16, 128))
        tables = jax.random.randint(ks[3], (2, 4), 0, 8)
        lengths = jnp.array([60, 33], jnp.int32)
        out = decode_attention(q, kp, vp, tables, lengths, softcap=30.0, interpret=True)
        ref = paged_decode_attention_ref(q, kp, vp, tables, lengths, softcap=30.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_length_mask_excludes_tail(self):
        """Changing K/V beyond `length` must not change the output."""
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (1, 1, 4, 128))
        kp = jax.random.normal(ks[1], (4, 1, 16, 128))
        vp = jax.random.normal(ks[2], (4, 1, 16, 128))
        tables = jnp.array([[0, 1, 2, 3]], jnp.int32)
        lengths = jnp.array([20], jnp.int32)
        out1 = decode_attention(q, kp, vp, tables, lengths, interpret=True)
        kp2 = kp.at[2:].set(999.0)
        vp2 = vp.at[2:].set(-999.0)
        out2 = decode_attention(q, kp2, vp2, tables, lengths, interpret=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


DIST_TEST = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.kernels.ring_all_gather.ops import ring_all_gather
from repro.kernels.ring_all_gather.ref import all_gather_ref
from repro.kernels.ring_all_to_all.ops import pallas_all_to_all
from repro.kernels.ring_all_to_all.ref import all_to_all_ref

N = 8
mesh = make_mesh((N,), ("x",))
for dtype in (jnp.float32, jnp.bfloat16):
    x = jax.random.normal(jax.random.PRNGKey(0), (N * 4, 128)).astype(dtype)
    for variant in ("pcpy", "b2b", "bcst", "bcst_b2b"):
        y = ring_all_gather(x, mesh, "x", variant=variant, interpret=True)
        assert np.array_equal(np.asarray(y), np.asarray(all_gather_ref(x, N))), (variant, dtype)
    xa = jax.random.normal(jax.random.PRNGKey(1), (N, N, 2, 128)).astype(dtype)
    for variant in ("per_round", "b2b"):
        y = pallas_all_to_all(xa, mesh, "x", variant=variant, interpret=True)
        assert np.array_equal(np.asarray(y), np.asarray(all_to_all_ref(xa))), (variant, dtype)
print("DIST_OK")
"""


def test_remote_dma_collective_kernels(subproc):
    out = subproc(DIST_TEST, n_devices=8)
    assert "DIST_OK" in out
