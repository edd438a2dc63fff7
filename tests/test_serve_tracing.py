"""The served path's profiler spans and counters: one miss and one hit batch
on the toy qwen2 preset, recorded by ``jax.profiler`` and read back from the
trace file.  The span names are what the benchmark's readers look for."""
import glob

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import build_model
from repro.serve.counters import counters
from repro.serve.engine import ServeEngine

B, S, NEW = 2, 48, 4

SPANS = ("serve.generate", "serve.first_token", "serve.kv.fetch", "serve.kv.fetch.h2d",
         "serve.cache.build", "serve.step.first", "serve.prefill", "serve.kv.pull",
         "serve.kv.save", "serve.first_logits", "serve.decode")


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _delta(c0: dict, c1: dict) -> dict:
    return {k: n - c0.get(k, 0) for k, n in c1.items()}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A warm engine serves one batch as a miss and then as a hit under the
    profiler; returns the results, counter deltas and the trace's events."""
    cfg = get_config("qwen2-0.5b").reduced()
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (B, S)).astype(np.int32)
    warm = [f"warm-{i}" for i in range(B)]
    untraced = {"miss": eng.generate(prompts, warm, NEW),
                "hit": eng.generate(prompts, warm, NEW)}
    keys = [f"ctx-{i}" for i in range(B)]
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        c0 = counters()
        miss = eng.generate(prompts, keys, NEW)
        c1 = counters()
        hit = eng.generate(prompts, keys, NEW)
        c2 = counters()
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0])
    host = [p for p in data.planes if p.name == "/host:CPU"][0]
    spans, programs = [], set()
    for line in host.lines:
        for ev in line.events:
            s = int(ev.start_ns)
            if ev.name.startswith("serve."):
                spans.append((ev.name, s, s + int(ev.duration_ns), _stats(ev)))
            elif line.name not in ("python", "python3"):
                programs.add(str(_stats(ev).get("hlo_module", "")))
    spans.sort(key=lambda t: (t[1], -t[2]))
    saved = sum(a.nbytes for key in keys for a in eng.store.saved(key).values())
    return dict(miss=miss, hit=hit, untraced=untraced, spans=spans, programs=programs,
                miss_counts=_delta(c0, c1), hit_counts=_delta(c1, c2), saved=saved)


def _named(spans, name):
    return [t for t in spans if t[0] == name]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _batches(served):
    """(miss spans, hit spans): the spans inside each ``serve.generate``."""
    gens = _named(served["spans"], "serve.generate")
    assert len(gens) == 2
    return [[t for t in served["spans"] if _inside(t, g)] for g in gens]


def test_every_span_is_recorded(served):
    assert {t[0] for t in served["spans"]} == set(SPANS)


def test_spans_nest(served):
    spans = served["spans"]
    for child, parent in (("serve.kv.fetch.h2d", "serve.kv.fetch"),
                          ("serve.kv.fetch", "serve.first_token"),
                          ("serve.step.first", "serve.first_token"),
                          ("serve.kv.pull", "serve.prefill"),
                          ("serve.prefill", "serve.first_token"),
                          ("serve.first_token", "serve.generate"),
                          ("serve.decode", "serve.generate")):
        parents = _named(spans, parent)
        for c in _named(spans, child):
            assert any(_inside(c, p) for p in parents), (child, parent)
    first = _named(spans, "serve.first_token")
    for name in ("serve.kv.save", "serve.first_logits", "serve.decode"):
        for t in _named(spans, name):
            assert not any(_inside(t, f) for f in first), name


def test_span_counts_per_batch(served):
    miss, hit = _batches(served)
    count = lambda spans, name: len(_named(spans, name))  # noqa: E731
    assert [t[3] for t in _named(miss, "serve.first_token")] == [{"batch": B, "hit": 0, "cache": "kv"}]
    assert [t[3] for t in _named(hit, "serve.first_token")] == [{"batch": B, "hit": 1, "cache": "kv"}]
    assert count(miss, "serve.kv.save") == B and count(miss, "serve.kv.fetch") == 0
    assert count(miss, "serve.prefill") == count(miss, "serve.kv.pull") == 1
    assert count(hit, "serve.kv.fetch") == B and count(hit, "serve.kv.save") == 0
    assert [t[3]["key"] for t in _named(hit, "serve.kv.fetch")] == [f"ctx-{i}" for i in range(B)]
    assert count(hit, "serve.kv.fetch.h2d") >= B
    for spans in (miss, hit):
        assert count(spans, "serve.decode") == count(spans, "serve.cache.build") == 1
        assert count(spans, "serve.first_logits") == 1


def test_first_token_span_is_the_ttft(served):
    miss, hit = _batches(served)
    for spans, res in ((miss, served["miss"]), (hit, served["hit"])):
        (_, s, e, _), = _named(spans, "serve.first_token")
        assert abs((e - s) / 1e9 - res.request_stats[0].ttft_wall_s) < 1e-3


def test_counters_count_the_bytes_moved(served):
    hit, miss, saved = served["hit_counts"], served["miss_counts"], served["saved"]
    assert hit["kv.fetch.to_device_bytes"] == saved
    assert hit.get("kv.fetch.to_host_bytes", 0) == 0
    assert hit["kv.fetch.tokens"] == B * S
    assert miss.get("kv.fetch.tokens", 0) == 0
    assert miss["kv.pull.to_host_bytes"] == saved and hit["kv.pull.to_host_bytes"] == 0
    assert hit["cache.rebuild.batches"] == miss["cache.rebuild.batches"] == 1
    assert miss["decode.host_syncs"] == hit["decode.host_syncs"] == NEW - 1
    assert miss["moe.held_dense.steps"] == hit["moe.held_dense.steps"] == 0     # no experts


def test_prefill_program_is_named(served):
    assert "jit_prefill" in served["programs"], sorted(served["programs"])


def test_profiler_does_not_change_tokens(served):
    for kind in ("miss", "hit"):
        np.testing.assert_array_equal(served[kind].tokens, served["untraced"][kind].tokens)
