"""Serving engine + host KV store: the one upload moves the saved bytes,
hits and misses get the same caches and generations; modeled-latency
ordering of the paper's fetch paths."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import attention as attn_mod, build_model
from repro.serve.counters import counters
from repro.serve.engine import ServeEngine
from repro.serve.host_store import HostKVStore
from repro.serve.kvcache import layer_major
from test_mla_moe import REF, SMALL, _model as _latent_model

KINDS = ("kv", "latent")


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("qwen2-0.5b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return ServeEngine(model, params), cfg


@pytest.fixture(scope="module")
def engines(engine):
    """By cache kind: (engine, vocab) of the reduced qwen2-0.5b (K/V) and
    of the small Moonlight (latent, with a leading dense layer)."""
    eng, cfg = engine
    return {"kv": (eng, cfg.vocab),
            "latent": (ServeEngine(_latent_model(), REF.make_params(SMALL, 3)),
                       SMALL["vocab_size"])}


def test_fetch_backends_bitwise_equal():
    store = HostKVStore()
    rng = np.random.default_rng(0)
    kb = rng.normal(size=(5, 16, 2, 2, 16)).astype(np.float32)
    vb = rng.normal(size=(5, 16, 2, 2, 16)).astype(np.float32)
    store.save("k", {"k": kb, "v": vb}, 70)
    res = store.fetch("k")
    for name in ("k", "v"):
        assert isinstance(res[name], jax.Array)
        np.testing.assert_array_equal(np.asarray(res[name]),
                                      layer_major(store.saved("k")[name])[:, None])
    # the MI300X model of the same fetch: batching beats per-block copies,
    # and the optimized command stream only tightens the latency
    from repro.core.dma import kv_fetch_schedule, mi300x_platform, simulate
    topo = mi300x_platform()
    block_bytes = kb[0].nbytes + vb[0].nbytes
    modeled = {b: simulate(kv_fetch_schedule(topo, 5, block_bytes, v), topo).latency
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
    pulled batch back with its tokens minor-most) come back as the
    layer-major batch of one that was saved."""
    from repro.serve.kvcache import to_blocks

    rng = np.random.default_rng(6)
    k, v = _batch(rng, memory_axes), _batch(rng, memory_axes)
    store = HostKVStore()
    kb, vb = to_blocks(k[:, 1:2]), to_blocks(v[:, 1:2])
    assert np.shares_memory(kb, k)
    store.save("ctx", {"k": kb, "v": vb}, 32)
    res = store.fetch("ctx")
    np.testing.assert_array_equal(np.asarray(res["k"]), k[:, 1:2])
    np.testing.assert_array_equal(np.asarray(res["v"]), v[:, 1:2])


def test_generation_identical_across_backends(engine):
    """A hit decodes the tokens of the miss that saved its contexts."""
    eng, cfg = engine
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab, (2, 40)).astype(np.int32)
    keys = ["a", "b"]
    miss = eng.generate(prompts, keys, 6)
    assert not miss.request_stats[0].cache_hit
    hit = eng.generate(prompts, keys, 6)
    assert hit.request_stats[0].cache_hit
    np.testing.assert_array_equal(hit.tokens, miss.tokens)


def _layer_by_layer(eng, keys, n_tokens, capacity):
    """The decode caches of the contexts saved under ``keys``, built from
    their host state one layer at a time, each layer uploaded on its own."""
    saved = [eng.store.saved(k) for k in keys]
    state = {name: np.stack([layer_major(s[name]) for s in saved], axis=1)[:, :, :n_tokens]
             for name in saved[0]}                                 # [L, B, S, ...]
    n_layers = next(iter(state.values())).shape[0]
    layers = [attn_mod.prefill_cache(
        eng.model.cfg, {name: jnp.asarray(a[i]) for name, a in state.items()}, capacity)
        for i in range(n_layers)]
    return eng.model.caches_from_layers(jax.tree.map(lambda *a: jnp.stack(a), *layers))


@pytest.mark.parametrize("S,capacity", [(48, 60), (48, 32), (40, 52)],
                         ids=["views", "views-rolling", "padded-copies"])
@pytest.mark.parametrize("source", ["hit", "miss"])
@pytest.mark.parametrize("kind", KINDS)
def test_hit_cache_equals_miss_cache(engines, kind, source, S, capacity):
    """The decode cache the device lays out, from a hit's fetched state or
    from a miss's prefill state, is bit for bit and in shape, dtype and
    placement the cache built one layer at a time from the saved host
    state: with room to spare, in a rolling window shorter than the
    context, and from block copies with a padded tail; for K/V, and for
    a latent split into leading dense and expert layers."""
    eng, vocab = engines[kind]
    prompts = np.random.default_rng(S + capacity).integers(
        0, vocab, (2, S)).astype(np.int32)
    keys = [f"cache-{kind}-{source}-{S}-{capacity}-{i}" for i in range(2)]
    *_, cache, _ = eng.first_token(prompts, keys, capacity=capacity)
    if source == "hit":
        cache = eng._rebuild_cache([eng.store.fetch(k) for k in keys], S, capacity)
    want = _layer_by_layer(eng, keys, S, capacity)
    assert jax.tree.structure(cache) == jax.tree.structure(want)
    for got, ref in zip(jax.tree.leaves(cache), jax.tree.leaves(want)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        assert got.sharding == ref.sharding
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert cache["blocks"][0]["kpos"].shape[-1] == capacity


@pytest.mark.parametrize("source", ["hit", "miss"])
@pytest.mark.parametrize("kind", KINDS)
def test_hit_moves_each_saved_byte_once(engines, kind, source, monkeypatch):
    """A hit uploads each saved byte once and copies none back; a miss
    pulls each byte of its state once.  Neither uploads a host array to
    build its cache: the device lays it out from what it holds."""
    eng, vocab = engines[kind]
    prompts = np.random.default_rng(4).integers(0, vocab, (2, 32)).astype(np.int32)
    keys = [f"once-{kind}-{source}-{i}" for i in range(2)]
    if source == "hit":
        eng.first_token(prompts, keys)
    build = eng._rebuild_jit

    def on_device_only(parts, **kw):
        assert all(isinstance(a, jax.Array) for a in jax.tree.leaves(parts))
        with jax.transfer_guard("disallow"):
            return build(parts, **kw)

    monkeypatch.setattr(eng, "_rebuild_jit", on_device_only)
    c0 = counters()
    out = eng.first_token(prompts, keys)
    moved = {k: n - c0.get(k, 0) for k, n in counters().items()}
    saved = sum(a.nbytes for k in keys for a in eng.store.saved(k).values())
    assert out[3][0].cache_hit == (source == "hit")
    if source == "hit":
        assert moved["kv.fetch.to_device_bytes"] == saved
        assert moved.get("kv.pull.to_host_bytes", 0) == 0
    else:
        assert moved["kv.pull.to_host_bytes"] == saved
        assert moved.get("kv.fetch.to_device_bytes", 0) == 0
    assert moved.get("kv.fetch.to_host_bytes", 0) == 0
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
    """The served path of chip_smoke.py (a miss, then a hit, with its
    bitwise, token and logit checks) on the toy preset."""
    from repro.launch import serve

    lines = []
    serve.run(get_config("qwen2-0.5b").reduced(), batch=2, ctx=48, new=3,
              log=lines.append)
    assert sum(line.startswith("[hit/") for line in lines) == 1
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
    with pytest.raises(ValueError):
        eng.store.fetch("ctx-z", "pcpy")


# ------------------------------------------------------------------------- #
# Modeled continuous-batching loop (DESIGN.md §12) at load -> 0             #
# ------------------------------------------------------------------------- #

def test_serving_simulator_unloaded_matches_fig16_exactly():
    """A lone request through the §12 batching loop reproduces the Fig. 16
    single-request TTFT bitwise: the K=1 composition is bit-identical to
    ``simulate``, and the loop adds the same batch-API/decode/framework
    terms ``serving_model.ttft`` does, in the same order."""
    from repro.core.serving_model import PAPER_LLMS, ttft
    from repro.core.serving_model import ServingConfig, ServingSimulator
    from repro.core.workload import Request

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
    from repro.core.serving_model import ServingConfig, ServingSimulator
    from repro.core.workload import Request

    spec = PAPER_LLMS[0]      # smallest model: the paper's best case
    sim = ServingSimulator(ServingConfig(spec=spec))
    req = Request(rid=0, arrival=0.0, prompt_tokens=4096, output_tokens=1)
    loop_ttft = sim.run([req]).timings[0].ttft
    assert loop_ttft == ttft(spec, 4096, "opt_b2b")["total"]
    speedup = ttft(spec, 4096, "pcpy")["total"] / loop_ttft
    assert 1.2 <= speedup <= 1.7    # fig16 total-TTFT band (paper: ~1.5x)


def test_serving_admission_validation():
    from repro.core.serving_model import ServingConfig, ServingSimulator

    with pytest.raises(ValueError):
        ServingSimulator(ServingConfig(admission="lifo"))
    with pytest.raises(ValueError):
        ServingSimulator().run([])
