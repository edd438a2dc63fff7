"""Serving engine + host KV store: all fetch backends move identical bytes
and produce identical generations; block math; modeled-latency ordering."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import build_model
from repro.serve.counters import counters
from repro.serve.engine import ServeEngine
from repro.serve.host_store import HostKVStore
from repro.serve.kvcache import layer_major

BACKENDS = ("pcpy", "b2b", "opt_b2b", "kernel")


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("qwen2-0.5b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return ServeEngine(model, params), cfg


def test_fetch_backends_bitwise_equal():
    store = HostKVStore()
    rng = np.random.default_rng(0)
    kb = rng.normal(size=(5, 16, 2, 2, 16)).astype(np.float32)
    vb = rng.normal(size=(5, 16, 2, 2, 16)).astype(np.float32)
    store.save("k", {"k": kb, "v": vb}, 70)
    res = {b: store.fetch("k", b) for b in BACKENDS}
    for b in BACKENDS:
        for name in ("k", "v"):
            assert isinstance(res[b].state[name], jax.Array)
            np.testing.assert_array_equal(np.asarray(res[b].state[name]),
                                          layer_major(store.saved("k")[name]))
    assert res["b2b"].n_transfers < res["pcpy"].n_transfers
    # the MI300X model of the same fetch: batching beats per-block copies,
    # and the optimized command stream only tightens the latency
    from repro.core.dma import kv_fetch_schedule, mi300x_platform, simulate
    topo = mi300x_platform()
    n_blocks, block_bytes = store.blocks_for("k")
    modeled = {b: simulate(kv_fetch_schedule(topo, n_blocks, block_bytes, v), topo).latency
               for b, v in (("pcpy", "pcpy"), ("b2b", "prelaunch_b2b"),
                            ("opt_b2b", "opt_prelaunch_b2b"))}
    assert modeled["b2b"] < modeled["pcpy"]
    assert modeled["opt_b2b"] < modeled["b2b"]


def _batch(rng, memory_axes):
    """K of a pulled batch [L=3, B=2, S=32, KV=2, hd=8] whose bytes lie in
    the order ``memory_axes`` gives its axes."""
    a = rng.normal(size=(3, 2, 32, 2, 8)).astype(np.float32)
    return np.ascontiguousarray(a.transpose(memory_axes)).transpose(np.argsort(memory_axes))


@pytest.mark.parametrize("memory_axes", [(0, 1, 2, 3, 4), (0, 1, 3, 4, 2), (2, 1, 0, 4, 3)],
                         ids=["c-order", "tokens-minor", "token-major"])
def test_fetch_reads_any_stored_layout(memory_axes):
    """Saved views of a batch whose memory is in any order (a TPU hands a
    pulled batch back with its tokens minor-most) come back, from every
    backend, as the layer-major K/V that was saved."""
    from repro.serve.kvcache import to_blocks

    rng = np.random.default_rng(6)
    k, v = _batch(rng, memory_axes), _batch(rng, memory_axes)
    store = HostKVStore()
    kb, vb = to_blocks(k[:, 1:2]), to_blocks(v[:, 1:2])
    assert np.shares_memory(kb, k)
    store.save("ctx", {"k": kb, "v": vb}, 32)
    for b in BACKENDS:
        res = store.fetch("ctx", b)
        np.testing.assert_array_equal(np.asarray(res.state["k"]), k[:, 1])
        np.testing.assert_array_equal(np.asarray(res.state["v"]), v[:, 1])


def test_engine_follows_kv_fetch_plan():
    """With no explicit fetch_backend, the engine uses the CommBackend plan:
    latte requests the optimized command stream (opt_b2b)."""
    store = HostKVStore()
    rng = np.random.default_rng(3)
    kb = rng.normal(size=(4, 16, 2, 2, 16)).astype(np.float32)
    vb = rng.normal(size=(4, 16, 2, 2, 16)).astype(np.float32)
    store.save("ctx", {"k": kb, "v": vb}, 60)
    n_blocks, block_bytes = store.blocks_for("ctx")
    assert n_blocks == 4 and block_bytes == kb[0].nbytes + vb[0].nbytes

    from repro.core.backend import CommBackend
    from repro.serve.engine import ServeEngine

    class _Probe(ServeEngine):      # plan resolution without model weights
        def __init__(self, comm, st):
            self.comm, self.store = comm, st

    assert _Probe(CommBackend("latte"), store)._planned_backend(["ctx"]) == "opt_b2b"
    assert _Probe(CommBackend("reference"), store)._planned_backend(["ctx"]) == "pcpy"


def test_generation_identical_across_backends(engine):
    eng, cfg = engine
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab, (2, 40)).astype(np.int32)
    keys = ["a", "b"]
    miss = eng.generate(prompts, keys, 6)
    assert not miss.request_stats[0].cache_hit
    for backend in ("pcpy", "b2b", "kernel"):
        hit = eng.generate(prompts, keys, 6, fetch_backend=backend)
        assert hit.request_stats[0].cache_hit
        np.testing.assert_array_equal(hit.tokens, miss.tokens)


def _cache_arrays(cache):
    (c,) = cache["blocks"]
    return {name: c[name] for name in ("k", "v", "kpos")}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("S,capacity", [(48, 60), (48, 32), (40, 52)],
                         ids=["views", "views-rolling", "padded-copies"])
def test_hit_cache_equals_miss_cache(engine, backend, S, capacity):
    """The decode cache the device lays out from a hit's fetched K/V is, bit
    for bit and in shape, dtype and placement, the cache the miss path
    builds from the pulled K/V: with room to spare, in a rolling window
    shorter than the context, and from block copies with a padded tail."""
    eng, cfg = engine
    prompts = np.random.default_rng(S + capacity).integers(
        0, cfg.vocab, (2, S)).astype(np.int32)
    keys = [f"cache-{backend}-{S}-{capacity}-{i}" for i in range(2)]
    *_, miss_cache, _ = eng.first_token(prompts, keys, capacity=capacity)
    hit_cache = eng._rebuild_cache([eng.store.fetch(k, backend) for k in keys], S, capacity)
    miss, hit = _cache_arrays(miss_cache), _cache_arrays(hit_cache)
    for name in miss:
        assert hit[name].shape == miss[name].shape and hit[name].dtype == miss[name].dtype
        assert hit[name].sharding == miss[name].sharding
        np.testing.assert_array_equal(np.asarray(hit[name]), np.asarray(miss[name]))
    assert hit["k"].shape[2] == capacity


@pytest.mark.parametrize("backend", BACKENDS)
def test_hit_moves_each_saved_byte_once(engine, backend):
    """A hit uploads each saved byte once, copies none back, and uploads no
    host array to build the cache: the device lays it out."""
    eng, cfg = engine
    prompts = np.random.default_rng(4).integers(0, cfg.vocab, (2, 32)).astype(np.int32)
    keys = [f"once-{backend}-{i}" for i in range(2)]
    eng.first_token(prompts, keys)
    saved = sum(a.nbytes for k in keys for a in eng.store.saved(k).values())
    c0 = counters()
    out = eng.first_token(prompts, keys, fetch_backend=backend)
    moved = {k: n - c0.get(k, 0) for k, n in counters().items()}
    assert out[3][0].cache_hit
    assert moved["kv.fetch.to_device_bytes"] == saved
    assert moved.get("kv.fetch.to_host_bytes", 0) == 0
    assert moved.get("cache.build.to_device_bytes", 0) == 0
    assert moved["cache.rebuild.batches"] == 1


def test_second_hit_batch_compiles_nothing(engine):
    """A hit's cache has the miss cache's shapes, dtypes and placement, so
    the decode step compiled for the miss serves it; and a second hit batch
    of the same shape compiles no program at all."""
    eng, cfg = engine
    rng = np.random.default_rng(5)
    batches = [(rng.integers(0, cfg.vocab, (2, 36)).astype(np.int32),
                [f"compile-{j}-{i}" for i in range(2)]) for j in range(2)]
    for prompts, keys in batches:
        eng.generate(prompts, keys, 3)                 # misses: prefill and decode
    decode_programs = eng._decode_jit._cache_size()
    eng.generate(*batches[0], 3)                       # first hit: compiles the rebuild
    assert eng._decode_jit._cache_size() == decode_programs
    rebuild_programs = eng._rebuild_jit._cache_size()

    compiled = []

    def listen(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        res = eng.generate(*batches[1], 3)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert res.request_stats[0].cache_hit
    assert compiled == []
    assert eng._rebuild_jit._cache_size() == rebuild_programs
    assert eng._decode_jit._cache_size() == decode_programs


def test_serve_entry_point_checks_pass_on_reduced_config():
    """The served path of chip_smoke.py (miss, then hits through every
    fetch backend, with its bitwise and logit checks) on the toy preset."""
    from repro.launch import serve

    lines = []
    serve.run(get_config("qwen2-0.5b").reduced(), batch=2, ctx=48, new=3,
              log=lines.append)
    assert sum(line.startswith("[hit/") for line in lines) == len(serve.FETCH_BACKENDS)
    assert sum(line.startswith("[check]") for line in lines) == 3


def test_requires_decoder_family():
    cfg = get_config("rwkv6-1.6b").reduced()
    model = build_model(cfg)
    with pytest.raises(ValueError):
        ServeEngine(model, None)


def test_store_membership_and_tokens(engine):
    eng, cfg = engine
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, cfg.vocab, (1, 24)).astype(np.int32)
    assert "ctx-z" not in eng.store
    eng.first_token(prompts, ["ctx-z"])
    assert "ctx-z" in eng.store
    assert eng.store.tokens_for("ctx-z") == 24


# ------------------------------------------------------------------------- #
# Modeled continuous-batching loop (DESIGN.md §12) at load -> 0             #
# ------------------------------------------------------------------------- #

def test_serving_simulator_unloaded_matches_fig16_exactly():
    """A lone request through the §12 batching loop reproduces the Fig. 16
    single-request TTFT bitwise: the K=1 composition is bit-identical to
    ``simulate``, and the loop adds the same batch-API/decode/framework
    terms ``serving_model.ttft`` does, in the same order."""
    from repro.core.serving_model import PAPER_LLMS, ttft
    from repro.serve.engine import ServingConfig, ServingSimulator
    from repro.serve.workload import Request

    sim = ServingSimulator(ServingConfig())
    for prompt, arrival, out in ((2048, 0.0, 1), (4096, 0.0, 1),
                                 (2048, 1.5, 4), (8192, 0.37, 8)):
        req = Request(rid=0, arrival=arrival, prompt_tokens=prompt,
                      output_tokens=out)
        got = sim.run([req]).timings[0].ttft
        want = ttft(PAPER_LLMS[2], prompt, "opt_b2b")["total"]
        assert got == want


def test_serving_simulator_unloaded_fig16_bands_still_hold():
    """Fig. 16's headline TTFT-speedup band, re-derived with the batching
    loop supplying the optimized-path numbers: loop-fed opt_b2b TTFT vs the
    closed-form pcpy baseline must still show the paper's GPU-side gain."""
    from repro.core.serving_model import PAPER_LLMS, ttft
    from repro.serve.engine import ServingConfig, ServingSimulator
    from repro.serve.workload import Request

    spec = PAPER_LLMS[0]      # smallest model: the paper's best case
    sim = ServingSimulator(ServingConfig(spec=spec))
    req = Request(rid=0, arrival=0.0, prompt_tokens=4096, output_tokens=1)
    loop_ttft = sim.run([req]).timings[0].ttft
    assert loop_ttft == ttft(spec, 4096, "opt_b2b")["total"]
    speedup = ttft(spec, 4096, "pcpy")["total"] / loop_ttft
    assert 1.2 <= speedup <= 1.7    # fig16 total-TTFT band (paper: ~1.5x)


def test_serving_admission_validation():
    from repro.serve.engine import ServingConfig, ServingSimulator

    with pytest.raises(ValueError):
        ServingSimulator(ServingConfig(admission="lifo"))
    with pytest.raises(ValueError):
        ServingSimulator().run([])
