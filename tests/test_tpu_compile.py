"""Compile the kernels and the decode step of the served path for a TPU v5e
that is described, not attached: the chip's compiler refuses what interpret
mode accepts (block shapes off the (8, 128) tiling, too much VMEM, programs
that do not fit).  Nothing runs, so these say nothing about results or
times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.paged_kv_gather.ops import gather_blocks
from repro.kernels.ring_all_gather.ops import VARIANTS as AG_VARIANTS
from repro.kernels.ring_all_gather.ops import ring_all_gather_fn
from repro.kernels.ring_all_to_all.ops import VARIANTS as AA_VARIANTS
from repro.kernels.ring_all_to_all.ops import pallas_all_to_all_fn
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve.engine import decode_capacity
from repro.serve.kvcache import BLOCK_TOKENS, blocks_for_tokens

QWEN = get_config("qwen2-0.5b")
BATCH, CTX, NEW = 8, 1024, 16          # the served batch of chip_smoke.py
HBM_BYTES = 16 * 10 ** 9               # one v5e chip
# one layer's held experts of Moonlight's share: wg/wu, then wd
HELD_SLICES = ("bf16[8,2048,1408]", "bf16[8,1408,2048]")


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compilation cache off:
    a compile for a described chip is written to it but cannot be read back
    without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return make_mesh((4,), ("x",), devices=topo.devices)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_kv_gather_compiles_at_qwen2_width(one_chip, dtype):
    n_blocks = blocks_for_tokens(CTX)
    d_kv = QWEN.n_layers * QWEN.n_kv_heads * QWEN.head_dim     # 3072
    pool = _sds((n_blocks, BLOCK_TOKENS, d_kv), dtype, one_chip)
    tbl = _sds((n_blocks,), jnp.int32, one_chip)
    compiled = gather_blocks.lower(pool, tbl, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("head_dim", [QWEN.head_dim, 128])
def test_paged_decode_attention_compiles(one_chip, dtype, head_dim):
    kv = QWEN.n_kv_heads
    g = QWEN.n_heads // kv
    mb = blocks_for_tokens(CTX + NEW)
    n_pool = BATCH * mb
    q = _sds((BATCH, kv, g, head_dim), dtype, one_chip)
    pool = _sds((n_pool, kv, BLOCK_TOKENS, head_dim), dtype, one_chip)
    tables = _sds((BATCH, mb), jnp.int32, one_chip)
    lengths = _sds((BATCH,), jnp.int32, one_chip)
    compiled = decode_attention.lower(q, pool, pool, tables, lengths).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant", AG_VARIANTS)
def test_ring_all_gather_compiles_on_2x2(mesh4, variant):
    x = _sds((4 * 2048, 128), jnp.float32, NamedSharding(mesh4, P("x", None)))
    compiled = ring_all_gather_fn(mesh4, "x", variant).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant", AA_VARIANTS)
def test_all_to_all_compiles_on_2x2(mesh4, variant):
    spec = NamedSharding(mesh4, P("x", None, None, None))
    x = _sds((4, 4, 512, 128), jnp.float32, spec)
    compiled = pallas_all_to_all_fn(mesh4, "x", variant).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen2_decode_step_compiles_and_fits_one_chip(one_chip):
    """The served decode step at qwen2-0.5b's published widths."""
    model = build_model(QWEN)
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(lambda: model.init_caches(BATCH, CTX + NEW + 1)))
    batch = {"tokens": _sds((BATCH, 1), jnp.int32, one_chip),
             "pos": _sds((), jnp.int32, one_chip)}
    compiled = jax.jit(model.decode_step).lower(params, batch, caches).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def _computations(hlo: str) -> dict[str, list[str]]:
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _copied_slices(hlo: str, slices, within=None) -> list[str]:
    """The instructions that put one of ``slices`` (result shapes, e.g. one
    layer's slice of a stacked array) in a buffer of its own: one outside
    any fusion whose result is such a slice, or a fusion whose computations
    make one and hold no dot (which the TPU compiler writes as a
    convolution) to read it in place.  ``within``: look only in these
    computations and those they call."""
    comps = _computations(hlo)
    called = lambda line: re.findall(  # noqa: E731
        r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", line)
    fused = {c for lines in comps.values() for line in lines if " fusion(" in line
             for c in called(line)}

    def body(name):
        return comps[name] + [x for line in comps[name] for c in called(line) for x in body(c)]

    names = set(comps)
    if within is not None:
        names, todo = set(), list(within)
        while todo:
            name = todo.pop()
            if name not in names:
                names.add(name)
                todo += [c for line in comps[name] for c in called(line)]

    def makes_slice(line):
        result = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) [a-z][\w\-]*\(", line)
        return bool(result) and any(s in result.group(1) for s in slices)

    copies = []
    for name in names:
        if name in fused:
            continue
        for line in comps[name]:
            if makes_slice(line):
                copies.append(line)
            elif " fusion(" in line:
                inner = [x for c in called(line) for x in body(c)]
                if (any(makes_slice(x) for x in inner)
                        and not any(re.search(r" (convolution|dot)\(", x) for x in inner)):
                    copies.append(line)
    return copies


def _moonlight_share(one_chip):
    """One chip's share of Moonlight-16B-A3B under 8-way expert parallelism
    (8 of 64 experts held, an eighth of the vocabulary) at its published
    widths: the model and its parameters' shapes on the described chip."""
    full = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(full, vocab=full.vocab // 8,
                              moe=dataclasses.replace(full.moe, held=(0, 8)))
    model = build_model(cfg)
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return model, params


def test_moonlight_share_decode_step_compiles_and_fits_one_chip(one_chip):
    """The served decode step of Moonlight's one-chip share: absorbed latent
    attention and the held experts in the dense form, over a latent cache of
    32 x 1153.  The expert dots read each layer's slice of the stacked
    weights in place: no grouped-matmul custom call, which would need the
    slice copied to a buffer of its own first."""
    model, params = _moonlight_share(one_chip)
    caches = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          jax.eval_shape(lambda: model.init_caches(32, 1024 + 128 + 1)))
    batch = {"tokens": _sds((32, 1), jnp.int32, one_chip),
             "pos": _sds((), jnp.int32, one_chip)}
    compiled = jax.jit(model.decode_step).lower(params, batch, caches).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    hlo = compiled.as_text()
    assert "while(" in hlo                                       # the layer scan
    assert "ragged" not in hlo
    copies = _copied_slices(hlo, HELD_SLICES)
    assert not copies, [line.strip()[:120] for line in copies]


def test_moonlight_share_prefill_keeps_grouped_matmuls(one_chip):
    """The same share's prefill at a small prompt, above the dense form's
    token bound: the held routes go through the grouped matmuls."""
    model, params = _moonlight_share(one_chip)
    tokens = _sds((1, 256), jnp.int32, one_chip)
    compiled = jax.jit(lambda p, t: model.forward(p, {"tokens": t}, want_cache=True,
                                                  remat=False)).lower(params, tokens).compile()
    assert "ragged" in compiled.as_text()


HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32"}


@pytest.mark.parametrize("cell", ["moonlight-share", "qwen2-0.5b"])
def test_decode_step_updates_the_donated_cache_in_place(one_chip, cell):
    """The served decode step as ``ServeEngine`` jits it, the cache donated,
    at a cell's batch and cache (Moonlight's share: 32 sequences over a
    latent cache; qwen2-0.5b: 16 over K/V; 1024 + 128 + 1 slots, as the
    engine lays them out).  The layer scan carries the stacked cache and
    writes only the new token into it: no instruction in its body makes a
    whole layer of a cache leaf a buffer of its own (the dots read it in
    place), and the output cache is the input's own buffers."""
    if cell == "moonlight-share":
        (model, params), batch = _moonlight_share(one_chip), 32
    else:
        model, batch = build_model(QWEN), 16
        params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                              jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), jax.eval_shape(
        lambda: model.init_caches(batch, decode_capacity(1024 + 128 + 1))))
    step = {"tokens": _sds((batch, 1), jnp.int32, one_chip),
            "pos": _sds((), jnp.int32, one_chip)}
    compiled = jax.jit(model.decode_step, donate_argnames="caches").lower(
        params, step, caches).compile()
    hlo = compiled.as_text()
    layers = {f"{HLO_DTYPE[a.dtype.name]}[{','.join(map(str, shape))}]"
              for a in jax.tree.leaves(caches) if a.ndim > 2          # not kpos, routes
              for shape in (a.shape[1:], (1,) + a.shape[1:])}
    scans = re.findall(r"body=%([\w.\-]+)", hlo)
    assert scans                                                  # the layer scan
    copies = _copied_slices(hlo, layers, within=scans)
    assert not copies, [line.strip()[:120] for line in copies]
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(caches))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
