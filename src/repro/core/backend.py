"""CommBackend: the paper's size-dispatched collective policy as a
first-class framework feature.

``CommBackend('latte')`` picks the implementation per message size using
thresholds re-derived from the DMA timing model on the TPU topology
(DESIGN.md §5); ``CommBackend('reference')`` always uses the XLA one-shot
collectives.  The serving engine's KV-fetch path consumes ``kv_fetch_plan``.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import tempfile
import warnings

from . import collectives as coll
from .dma.dispatch import DispatchEntry, derive_dispatch
from .dma.topology import (Topology, mi300x_cluster, tpu_v5e_multislice,
                           tpu_v5e_pod)

KB = 1024
MB = 1024 * 1024

# Bump when the simulator/calibration changes in a way that invalidates
# previously derived dispatch tables.
# v2: optimized command streams (DESIGN.md §7) — new Calibration constants
# (control_batched/doorbell_batched/fused_sync/sync_obs_batched).
# v3: chunked command streams (DESIGN.md §8) — Calibration.max_chunk_bytes
# and the swept chunk granularities join the fingerprint, entries carry a
# per-range ``chunk``; stale v2 tables must never serve chunked sweeps.
# v4: pipelined ring collectives (DESIGN.md §9) — the sweep offers the
# per-chunk-signaled ``pipe_`` family (allow_pipelined), so v3 tables that
# never saw those candidates must miss and re-derive (regression-tested in
# tests/test_dispatch_cache.py).
# v5: reduce collectives (DESIGN.md §10) — bundled tables grow reduce_scatter
# and all_reduce sweeps (allow_reduce) and the reduce calibration
# (Calibration.reduce_setup / reduce_bytes_per_s, embedded via topo!r) joins
# the fingerprint; v4 tables carry neither, so they must miss and re-derive
# (regression-tested in tests/test_dispatch_cache.py).
# v6: hierarchical multi-node collectives (DESIGN.md §11) — bundled tables
# grow the tpu64/tpu256/mi300x-2node hier sweeps and the NIC calibration
# (Calibration.nic_latency / nic_bytes_per_s, embedded via topo!r) joins the
# fingerprint; v5 tables never saw the hier candidates or the NIC tier, so
# they must miss and re-derive (regression-tested in
# tests/test_dispatch_cache.py).
# v7: fused compute-collective overlap (DESIGN.md §15) — the CU calibration
# (Calibration.cu_tile_setup / cu_flops, embedded via topo!r) joins the
# fingerprint, and the single-node latte sweeps re-derive with the
# optimized/prelaunch command streams offered (allow_optimized), retiring
# the unconditional StaleTablesWarning; v6 baseline-only tables never saw
# the opt_ candidates, so they must miss and re-derive (regression-tested
# in tests/test_dispatch_cache.py).
_TABLE_CACHE_VERSION = 7
# The size sweep behind every cached/bundled table; part of the cache key.
_SWEEP_SIZES = [2 ** i for i in range(10, 31)]
# Chunk granularities the table sweep offers the argmin (DESIGN.md §8.1):
# the calibrated default (None) plus a finer split; part of the cache key.
_SWEEP_CHUNKS = (None, 1 * MB)
_TABLE_CACHE_DIR = os.environ.get(
    "REPRO_DISPATCH_CACHE",
    os.path.join(tempfile.gettempdir(), "repro-dma-dispatch"))


# Pre-derived tables shipped with the package (regenerate with
# `python -m repro.core.backend`); keyed by the same fingerprint as the disk
# cache, so any simulator/calibration change simply misses and re-derives.
_BUNDLED_TABLES = os.path.join(os.path.dirname(__file__), "dma",
                               "_dispatch_tables.json")


def _table_key(topo: Topology, sizes: list[int]) -> str:
    # topo!r embeds the full Calibration (including max_chunk_bytes and the
    # chunking-relevant issue constants), so any recalibration — not just a
    # version bump — misses the cache and re-derives.
    return hashlib.sha1(
        f"v{_TABLE_CACHE_VERSION}|{topo!r}|{sizes!r}|{_SWEEP_CHUNKS!r}"
        .encode()).hexdigest()[:16]


def _table_cache_path(topo: Topology, sizes: list[int]) -> str:
    return os.path.join(_TABLE_CACHE_DIR,
                        f"tables_{topo.name}_{_table_key(topo, sizes)}.json")


def _parse_tables(raw):
    return tuple(
        tuple(DispatchEntry(e["lo"], e["hi"], e["variant"], e.get("chunk"))
              for e in tbl)
        for tbl in raw)


def _load_table_cache(topo: Topology, sizes: list[int]):
    """Cross-process memo of the derived tables: subprocesses (tests, dry
    runs, serving workers) skip the argmin sweep entirely on a warm cache.
    The bundled package copy serves cold starts."""
    try:
        with open(_BUNDLED_TABLES) as f:
            bundled = json.load(f)
        raw = bundled.get(_table_key(topo, sizes))
        if raw is not None:
            return _parse_tables(raw)
    except (OSError, ValueError, KeyError):
        pass
    try:
        with open(_table_cache_path(topo, sizes)) as f:
            return _parse_tables(json.load(f))
    except (OSError, ValueError, KeyError):
        return None


def _serialize_tables(tables):
    return [[{"lo": e.lo, "hi": e.hi, "variant": e.variant, "chunk": e.chunk}
             for e in tbl] for tbl in tables]


def _store_table_cache(topo: Topology, sizes: list[int], tables) -> None:
    try:
        os.makedirs(_TABLE_CACHE_DIR, exist_ok=True)
        path = _table_cache_path(topo, sizes)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(_serialize_tables(tables), f)
        os.replace(tmp, path)
    except OSError:
        pass

# Variant names (paper + torus ring renderings) -> JAX implementations here.
# The pipe_ winners (DESIGN.md §9) map onto the matching JAX ring renderings:
# XLA already software-pipelines the lowered ring loop, so the per-chunk
# simulator variant and the JAX collective share one implementation.
_AG_IMPL = {
    "pcpy": coll.reference_all_gather,
    "b2b": coll.ring_all_gather,
    "bcst": coll.bidir_ring_all_gather,
    "ring": coll.ring_all_gather,
    "bidir_ring": coll.bidir_ring_all_gather,
    "pipe_b2b": coll.ring_all_gather,
    "pipe_bidir_ring": coll.bidir_ring_all_gather,
    # Hierarchical winners (DESIGN.md §11): XLA lowers a multislice
    # all-gather to exactly the two-tier decomposition the hier_ variants
    # model (intra-slice ring + DCN exchange), so both map onto the ring
    # rendering — the dispatch *threshold* is what the table contributes.
    "hier_ring": coll.ring_all_gather,
    "hier_pipe": coll.ring_all_gather,
}
_AA_IMPL = {
    "pcpy": coll.reference_all_to_all,
    "b2b": coll.pairwise_all_to_all,
    "swap": coll.pairwise_all_to_all,
    "ring": coll.pairwise_all_to_all,
    "pipe_b2b": coll.pairwise_all_to_all,
}
# Reduce winners (DESIGN.md §10): every ring reduce variant — including the
# bidir and per-chunk-pipelined renderings — lowers to the ppermute ring
# reduce-scatter (XLA fuses the per-step accumulate into the loop); the
# all-reduce composition lowers to its RS + ring-AG decomposition.
_RS_IMPL = {
    "ring_rs": coll.ring_reduce_scatter,
    "bidir_ring_rs": coll.ring_reduce_scatter,
    "pipe_ring_rs": coll.ring_reduce_scatter,
    "pipe_bidir_ring_rs": coll.ring_reduce_scatter,
    "hier_ring_rs": coll.ring_reduce_scatter,
    "hier_pipe_rs": coll.ring_reduce_scatter,
}
_AR_IMPL = {
    "ring_rs": coll.ring_all_reduce,
    "bidir_ring_rs": coll.ring_all_reduce,
    "pipe_ring_rs": coll.ring_all_reduce,
    "pipe_bidir_ring_rs": coll.ring_all_reduce,
    "hier_ring_rs": coll.ring_all_reduce,
    "hier_pipe_rs": coll.ring_all_reduce,
}

# Position of each collective's table in the (ag, aa, rs, ar) tuple.
_TABLE_INDEX = {"all_gather": 0, "all_to_all": 1, "reduce_scatter": 2,
                "all_reduce": 3}


def _derive_single_node(topo: Topology):
    """Derive the (ag, aa, rs, ar) latte tables for one single-node topology.

    Since v7 the sweep offers the full ``opt_``/``prelaunch_`` composition
    alongside the pipelined rings, so ``CommBackend('latte')`` dispatches on
    current winners instead of the baseline-only published thresholds (the
    paper's as-published Tables 2/3 remain reproducible through the default
    ``derive_dispatch`` flags — this is the *deployment* table).
    """
    sizes = _SWEEP_SIZES
    kw = dict(allow_pipelined=True, allow_optimized=True,
              chunk_sizes=_SWEEP_CHUNKS)
    ag = tuple(derive_dispatch(topo, "all_gather", sizes, **kw))
    aa = tuple(derive_dispatch(topo, "all_to_all", sizes, **kw))
    rs = tuple(derive_dispatch(topo, "reduce_scatter", sizes,
                               allow_reduce=True, **kw))
    ar = tuple(derive_dispatch(topo, "all_reduce", sizes,
                               allow_reduce=True, **kw))
    return ag, aa, rs, ar


@functools.lru_cache(maxsize=8)
def tpu_dispatch_tables(n_devices: int = 16):
    """Re-derive Tables 2/3 for the TPU torus from the timing model
    (DESIGN.md §4), plus the reduce_scatter/all_reduce tables (§10): the
    event simulator routes every variant over real ICI neighbor links, so
    the argmin picks between direct multi-hop one-shot schedules and the
    ring/bidir-ring renderings with true per-step dependencies — since v7
    with the ``opt_``/``prelaunch_`` command streams offered too.  Returns
    ``(ag, aa, rs, ar)`` entry tuples.  The sweep is memoized in-process
    (dispatch.derive_dispatch) and on disk (seconds per fresh process
    otherwise)."""
    topo = tpu_v5e_pod(n_devices)
    sizes = _SWEEP_SIZES
    cached = _load_table_cache(topo, sizes)
    if cached is not None:
        return cached
    tables = _derive_single_node(topo)
    _store_table_cache(topo, sizes, tables)
    return tables


#: Multi-node topology builders the bundled v6 tables cover (DESIGN.md §11):
#: 4- and 16-slice TPU v5e multislices plus a 2-node MI300X RDMA cluster.
MULTINODE_TOPOS = {
    "tpu64": lambda: tpu_v5e_multislice(64),
    "tpu256": lambda: tpu_v5e_multislice(256),
    "mi300x-2node": lambda: mi300x_cluster(2),
}


def _derive_multinode(topo: Topology):
    """Derive the (ag, rs, ar) tables for one multi-node topology.

    No all_to_all sweep — it has no hierarchical rendering and raises
    (DESIGN.md §11).  The hier sweep offers the full ``opt_``/``prelaunch_``
    composition: unlike the single-node paper tables (kept baseline-only so
    Tables 2/3 stay reproducible as published) there is no published
    multi-node baseline to preserve, so the table should simply be the best
    modeled stream.  Only derivable in CI budgets because every hier
    candidate runs the vectorized sweep fast path (DESIGN.md §11.3).
    """
    sizes = _SWEEP_SIZES
    kw = dict(allow_pipelined=True, allow_optimized=True,
              chunk_sizes=_SWEEP_CHUNKS)
    ag = tuple(derive_dispatch(topo, "all_gather", sizes, **kw))
    rs = tuple(derive_dispatch(topo, "reduce_scatter", sizes,
                               allow_reduce=True, **kw))
    ar = tuple(derive_dispatch(topo, "all_reduce", sizes,
                               allow_reduce=True, **kw))
    return ag, rs, ar


@functools.lru_cache(maxsize=8)
def multinode_dispatch_tables(spec: str = "tpu64"):
    """Hierarchical dispatch tables for a multi-node topology (DESIGN.md
    §11): ``(ag, rs, ar)`` entry tuples for a :data:`MULTINODE_TOPOS` spec.
    Same cache discipline as :func:`tpu_dispatch_tables` — in-process memo,
    disk cache, bundled package copy keyed by the v6 fingerprint."""
    topo = MULTINODE_TOPOS[spec]()
    sizes = _SWEEP_SIZES
    cached = _load_table_cache(topo, sizes)
    if cached is not None:
        return cached
    tables = _derive_multinode(topo)
    _store_table_cache(topo, sizes, tables)
    return tables


def _pick(entries, size: int) -> str:
    for e in entries:
        if size >= e.lo and (e.hi is None or size < e.hi):
            return e.variant
    return entries[-1].variant


class StaleTablesWarning(UserWarning):
    """The bundled dispatch tables predate this simulator/calibration.

    The bundled ``_dispatch_tables.json`` is keyed by a fingerprint of the
    table-cache version, the topology's full calibration, and the sweep
    grid.  When the key for the current simulator is absent — a calibration
    changed, the cache version was bumped, or the bundled copy was never
    regenerated — the latte backend still dispatches on *correct* tables
    (it re-derives on the fly, paying the argmin sweep once per process),
    but the shipped thresholds are genuinely stale and the package should
    be regenerated with ``python -m repro.core.backend``.  Pass
    ``CommBackend(allow_stale_tables=True)`` to acknowledge and silence.
    """


@functools.lru_cache(maxsize=32)
def _bundled_current(topo: Topology, sizes: tuple[int, ...]) -> bool:
    """True when the bundled package tables carry this fingerprint —
    i.e. they were regenerated against the current simulator/calibration."""
    try:
        with open(_BUNDLED_TABLES) as f:
            return _table_key(topo, list(sizes)) in json.load(f)
    except (OSError, ValueError):
        return False


@dataclasses.dataclass(frozen=True)
class CommBackend:
    kind: str = "latte"            # latte | reference
    axis_devices: int = 16
    b2b_fanout_threshold: int = 4 * MB   # paper §5.3.1 empirical threshold
    # Dispatching against a bundled-tables fingerprint mismatch (simulator
    # or calibration drifted since `python -m repro.core.backend` last ran)
    # warns (StaleTablesWarning) unless explicitly acknowledged here.
    allow_stale_tables: bool = False

    def _strip(self, v: str) -> str:
        # opt_/prelaunch_ change the command stream's scheduling envelope,
        # not which JAX collective implements the winner.
        for prefix in ("opt_", "prelaunch_"):
            if v.startswith(prefix):
                v = v[len(prefix):]
        return v

    def _tables(self, collective: str):
        topo = tpu_v5e_pod(self.axis_devices)
        if not self.allow_stale_tables and \
                not _bundled_current(topo, tuple(_SWEEP_SIZES)):
            warnings.warn(
                f"CommBackend('latte').{collective}: the bundled dispatch "
                "tables do not match this simulator/calibration fingerprint "
                f"(v{_TABLE_CACHE_VERSION}) — re-deriving on the fly; "
                "regenerate with `python -m repro.core.backend` or pass "
                "allow_stale_tables=True to acknowledge",
                StaleTablesWarning, stacklevel=3)
        return tpu_dispatch_tables(self.axis_devices)

    def _dispatch(self, collective: str, impls: dict, x, axis_name: str,
                  size: int):
        """Run the table's winner at ``size`` bytes.  A winner with no JAX
        implementation is an error, never a silent fall back to XLA."""
        table = self._tables(collective)[_TABLE_INDEX[collective]]
        variant = self._strip(_pick(table, size))
        if variant not in impls:
            raise ValueError(f"CommBackend('latte').{collective}: dispatch "
                             f"winner {variant!r} has no JAX implementation")
        return impls[variant](x, axis_name)

    def all_gather(self, x, axis_name: str):
        """Called inside shard_map.  Returns stacked [n, *x.shape]."""
        if self.kind == "reference":
            return coll.reference_all_gather(x, axis_name)
        size = x.size * x.dtype.itemsize * self.axis_devices
        return self._dispatch("all_gather", _AG_IMPL, x, axis_name, size)

    def all_to_all(self, x, axis_name: str):
        """Called inside shard_map with x: [n, ...] chunks."""
        if self.kind == "reference":
            return coll.reference_all_to_all(x, axis_name)
        size = x.size * x.dtype.itemsize
        return self._dispatch("all_to_all", _AA_IMPL, x, axis_name, size)

    def reduce_scatter(self, x, axis_name: str):
        """Called inside shard_map with x: [n, ...] addend chunks; returns
        this device's reduced chunk (DESIGN.md §10)."""
        if self.kind == "reference":
            return coll.reference_reduce_scatter(x, axis_name)
        size = x.size * x.dtype.itemsize
        return self._dispatch("reduce_scatter", _RS_IMPL, x, axis_name, size)

    def all_reduce(self, x, axis_name: str):
        """Called inside shard_map with x: [n, ...] chunks; returns the
        elementwise sum across devices (DESIGN.md §10)."""
        if self.kind == "reference":
            return coll.reference_all_reduce(x, axis_name)
        size = x.size * x.dtype.itemsize
        return self._dispatch("all_reduce", _AR_IMPL, x, axis_name, size)

    def kv_fetch_plan(self, n_blocks: int, block_bytes: int) -> dict:
        """How a modeled server should fetch dispersed KV blocks (§5.3).

        The latte plan additionally requests the optimized command stream
        (``optimized: True`` — batched submission + fused write+signal on
        the batch's chunk commands, DESIGN.md §7/§8);
        ``serving_model.ServingSimulator`` maps it to the
        ``opt_prelaunch_b2b`` fetch schedule.
        """
        total = n_blocks * block_bytes
        if self.kind == "reference":
            return {"mode": "pcpy", "fanout": min(n_blocks, 16),
                    "optimized": False}
        if total < self.b2b_fanout_threshold:
            return {"mode": "b2b", "fanout": 1, "optimized": True}
        return {"mode": "b2b", "fanout": 4, "optimized": True}


def regenerate_bundled_tables(device_counts=(16,),
                              multinode=tuple(MULTINODE_TOPOS)) -> str:
    """Derive the standard TPU dispatch tables plus the multi-node hier
    tables (DESIGN.md §11) and write the bundled package copy
    (`python -m repro.core.backend`).  Run after any simulator or
    calibration change (and bump _TABLE_CACHE_VERSION if the key inputs did
    not change but the semantics did).  Also writes through to the disk
    cache ($REPRO_DISPATCH_CACHE) so CI can upload the sweep artifact."""
    out = {}
    for spec in multinode:
        topo = MULTINODE_TOPOS[spec]()
        tables = _derive_multinode(topo)
        _store_table_cache(topo, _SWEEP_SIZES, tables)
        out[_table_key(topo, _SWEEP_SIZES)] = _serialize_tables(tables)
    for n in device_counts:
        topo = tpu_v5e_pod(n)
        tables = _derive_single_node(topo)
        _store_table_cache(topo, _SWEEP_SIZES, tables)
        out[_table_key(topo, _SWEEP_SIZES)] = _serialize_tables(tables)
    with open(_BUNDLED_TABLES, "w") as f:
        json.dump(out, f, indent=1)
    return _BUNDLED_TABLES


if __name__ == "__main__":
    print(f"wrote {regenerate_bundled_tables()}")
