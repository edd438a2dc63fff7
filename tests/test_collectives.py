"""JAX-level latte collectives vs XLA references (8 emulated devices,
subprocess) + CommBackend dispatch behavior."""
import types
import warnings

import pytest

from repro.core import backend
from repro.core.backend import (CommBackend, StaleTablesWarning,
                                tpu_dispatch_tables)


LATTE_TEST = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import collectives as coll
from repro.core.backend import CommBackend

N = 8
mesh = make_mesh((N,), ("x",))

x = jax.random.normal(jax.random.PRNGKey(0), (N, 4, 32), jnp.float32)
def wrap_ag(fn):
    f = jax.shard_map(lambda a: fn(a[0], "x"), mesh=mesh, in_specs=P("x", None, None),
                  out_specs=P(None, None, None), check_vma=False)
    return np.asarray(jax.jit(f)(x))
ref = np.asarray(x)
for name, fn in (("ring", coll.ring_all_gather),
                 ("bidir", coll.bidir_ring_all_gather),
                 ("reference", coll.reference_all_gather)):
    assert np.allclose(wrap_ag(fn), ref), name

xa = jax.random.normal(jax.random.PRNGKey(1), (N, N, 2, 16), jnp.float32)
def wrap_aa(fn):
    f = jax.shard_map(lambda a: fn(a[0], "x")[None], mesh=mesh,
                  in_specs=P("x", None, None, None),
                  out_specs=P("x", None, None, None), check_vma=False)
    return np.asarray(jax.jit(f)(xa))
expect = np.swapaxes(np.asarray(xa), 0, 1)
assert np.allclose(wrap_aa(coll.pairwise_all_to_all), expect)
assert np.allclose(wrap_aa(coll.reference_all_to_all), expect)

# reduce collectives (DESIGN.md §10): ring RS/AR vs the XLA references
xr = jax.random.normal(jax.random.PRNGKey(2), (N, N, 2, 8), jnp.float32)
expect_rs = np.asarray(xr).sum(axis=0)          # row i = device i's chunk
def wrap_rs(fn):
    f = jax.shard_map(lambda a: fn(a[0], "x")[None], mesh=mesh,
                  in_specs=P("x", None, None, None),
                  out_specs=P("x", None, None), check_vma=False)
    return np.asarray(jax.jit(f)(xr))
assert np.allclose(wrap_rs(coll.ring_reduce_scatter), expect_rs, atol=1e-4)
assert np.allclose(wrap_rs(coll.reference_reduce_scatter), expect_rs, atol=1e-4)
def wrap_ar(fn):
    f = jax.shard_map(lambda a: fn(a[0], "x"), mesh=mesh,
                  in_specs=P("x", None, None, None),
                  out_specs=P(None, None, None), check_vma=False)
    return np.asarray(jax.jit(f)(xr))
assert np.allclose(wrap_ar(coll.ring_all_reduce), expect_rs, atol=1e-4)
assert np.allclose(wrap_ar(coll.reference_all_reduce), expect_rs, atol=1e-4)

# CommBackend end-to-end inside shard_map (size-dispatched); stale-table
# acknowledgment keeps the subprocess log warning-free (test_backend covers
# the warning itself).
be = CommBackend("latte", axis_devices=N, allow_stale_tables=True)
y = np.asarray(jax.jit(jax.shard_map(lambda a: be.all_gather(a[0], "x"),
      mesh=mesh, in_specs=P("x", None, None), out_specs=P(None, None, None),
      check_vma=False))(x))
assert np.allclose(y, ref)
z = np.asarray(jax.jit(jax.shard_map(lambda a: be.reduce_scatter(a[0], "x")[None],
      mesh=mesh, in_specs=P("x", None, None, None),
      out_specs=P("x", None, None), check_vma=False))(xr))
assert np.allclose(z, expect_rs, atol=1e-4)
w = np.asarray(jax.jit(jax.shard_map(lambda a: be.all_reduce(a[0], "x"),
      mesh=mesh, in_specs=P("x", None, None, None),
      out_specs=P(None, None, None), check_vma=False))(xr))
assert np.allclose(w, expect_rs, atol=1e-4)
print("LATTE_OK")
"""


@pytest.mark.slow
def test_latte_collectives_match_reference(subproc):
    assert "LATTE_OK" in subproc(LATTE_TEST, n_devices=8)


CHECK_TEST = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch import collectives_check as cc
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("x",))
n = cc.run(mesh, "x", cases=((4096, jnp.float32), (4096, jnp.bfloat16)),
           log=lambda *_: None)
assert n == 2 * (7 + 4 + 2 + 2), n
# the comparison is not vacuous: one flipped element shows on its device only
a = jnp.zeros((4, 4, 2, 128), jnp.float32)
bad = np.asarray(cc._mismatch_fn(mesh, "x", P("x", None, None, None))(a, a.at[2, 0, 0, 0].set(1.0)))
assert bad.tolist() == [0, 0, 1, 0], bad
print("CHECK_OK")
"""


def test_collectives_check_on_four_devices(subproc):
    """The four-chip phase of chip_smoke.py, at 4 KB per device on four
    emulated CPU devices (Pallas kernels in interpret mode)."""
    assert "CHECK_OK" in subproc(CHECK_TEST, n_devices=4)


def test_dispatch_tables_structure():
    ag, aa, rs, ar = tpu_dispatch_tables(16)
    assert ag[0].lo == 1024 and ag[-1].hi is None
    # contiguous, non-overlapping
    for a, b in zip(ag, ag[1:]):
        assert a.hi == b.lo
    # v7 tables sweep the full single-node variant space (opt_/prelaunch_/
    # pipe_), so the latency-bound winner is an optimized prelaunched stream
    # rather than the baseline b2b of the v6 baseline-only sweep.
    assert ag[0].variant.startswith("opt_")
    # reduce tables (DESIGN.md §10) carry reduce-family winners only
    for table in (rs, ar):
        assert table[0].lo == 1024 and table[-1].hi is None
        for a, b in zip(table, table[1:]):
            assert a.hi == b.lo
        assert all(e.variant.endswith("_rs") for e in table)


class _AnyImpl(dict):
    """Stands in for the _*_IMPL maps: any winner resolves to a stub so the
    dispatch path runs outside shard_map."""

    def __contains__(self, key):
        return True

    def __getitem__(self, key):
        return lambda x, axis_name: ("dispatched", key)


def _stub_array(nbytes: int):
    return types.SimpleNamespace(size=nbytes,
                                 dtype=types.SimpleNamespace(itemsize=1))


def test_latte_dispatch_silent_on_current_tables(monkeypatch):
    """The bundled tables are re-derived with the full single-node variant
    space (v7), so the default latte backend dispatches on current winners
    without warning."""
    monkeypatch.setattr(backend, "_AG_IMPL", _AnyImpl())
    be = CommBackend("latte")
    with warnings.catch_warnings():
        warnings.simplefilter("error", StaleTablesWarning)
        out = be.all_gather(_stub_array(1 << 20), "x")
    assert out[0] == "dispatched"


def test_latte_dispatch_warns_on_stale_fingerprint(monkeypatch, tmp_path):
    """A genuinely stale bundled fingerprint must stay loud: when the
    bundled tables miss the current key the default backend re-derives on
    the fly AND warns."""
    monkeypatch.setattr(backend, "_AG_IMPL", _AnyImpl())
    be = CommBackend("latte")
    be.all_gather(_stub_array(1 << 20), "x")    # warm the table memo
    monkeypatch.setattr(backend, "_BUNDLED_TABLES", str(tmp_path / "gone.json"))
    backend._bundled_current.cache_clear()
    try:
        with pytest.warns(StaleTablesWarning, match="do not match this"):
            out = be.all_gather(_stub_array(1 << 20), "x")
        assert out[0] == "dispatched"   # still returns the table's winner
        # acknowledging silences it even on a stale fingerprint
        acked = CommBackend("latte", allow_stale_tables=True)
        with warnings.catch_warnings():
            warnings.simplefilter("error", StaleTablesWarning)
            out = acked.all_gather(_stub_array(1 << 20), "x")
        assert out[0] == "dispatched"
    finally:
        backend._bundled_current.cache_clear()


def test_latte_dispatch_raises_on_unmapped_winner(monkeypatch):
    """A table winner with no JAX implementation must fail loudly rather
    than run XLA's collective in its place."""
    monkeypatch.setattr(backend, "_AG_IMPL", {})
    with pytest.raises(ValueError, match="no JAX implementation"):
        CommBackend("latte").all_gather(_stub_array(1 << 20), "x")


@pytest.mark.parametrize("n_devices", [4, 8])
def test_every_table_winner_is_mapped(n_devices):
    """Every winner in the derived 4- and 8-device tables has an
    implementation (test_dispatch_cache covers the bundled 16-device ones),
    so raising on an unmapped winner changes no dispatch."""
    be = CommBackend("latte", axis_devices=n_devices)
    impls = (backend._AG_IMPL, backend._AA_IMPL, backend._RS_IMPL,
             backend._AR_IMPL)
    for table, impl in zip(tpu_dispatch_tables(n_devices), impls):
        assert {be._strip(e.variant) for e in table} <= set(impl)


def test_reference_backend_never_consults_tables():
    ref = CommBackend("reference")
    with warnings.catch_warnings():
        warnings.simplefilter("error", StaleTablesWarning)
        ref.kv_fetch_plan(16, 16 * 1024)


def test_kv_fetch_plan_threshold():
    be = CommBackend("latte")
    small = be.kv_fetch_plan(16, 16 * 1024)
    big = be.kv_fetch_plan(1024, 64 * 1024)
    assert small == {"mode": "b2b", "fanout": 1, "optimized": True}
    assert big["fanout"] > 1
    assert big["optimized"]     # latte plans the optimized command stream
    ref = CommBackend("reference")
    ref_plan = ref.kv_fetch_plan(16, 16 * 1024)
    assert ref_plan["mode"] == "pcpy"
    assert not ref_plan["optimized"]
