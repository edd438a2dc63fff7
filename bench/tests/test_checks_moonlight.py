"""The comparison that decides ``correct`` in the latent-attention MoE cell,
driven through the harness on the CPU: a sound run passes its limits; one
altered token, a decode step that returns its cache unchanged, routes
dropped over a capacity of 1.25 and the control (the reference in fp8)
each fail one of them.

The sizes keep every published width, the router's 64 experts and the 8
held; they cut depth to the dense layer and two MoE layers, the vocabulary,
prompt length and batch, so that a run fits a test.  The limits are the
cell's own, from its configuration file."""
from __future__ import annotations

import dataclasses
import time

import jax
import pytest

import run as R

SEED = 2**31 + 77
CELL = "serve-hit.moonlight-16b-a3b-ep8"
CUTS = ({"num_hidden_layers": 3, "vocab_size": 2048},
        {"batch": 2, "prompt_tokens": 128, "new_tokens": 8, "pool_batches": 2,
         "sample_requests": 4})


def _cell():
    spec, cell, config, traffic = R.load_cell(CELL)
    R.prepare_imports()
    return spec, cell, dict(config, **CUTS[0]), dict(traffic, **CUTS[1])


def _run():
    spec, cell, config, traffic = _cell()
    return R.run_cell(spec, cell, config, traffic, seed=SEED, seconds=1.0, trace=False,
                      devices=jax.devices()[:1], peaks=None, t_start=time.perf_counter())


def test_moonlight_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]


def test_moonlight_altered_token_fails(monkeypatch):
    from repro.serve.engine import ServeEngine

    orig = ServeEngine.generate

    def altered(self, prompts, keys, n_new, **kw):
        res = orig(self, prompts, keys, n_new, **kw)
        vocab = self.model.cfg.vocab
        res.tokens[:, -1] = (res.tokens[:, -1] + vocab // 2) % vocab
        return res

    monkeypatch.setattr(ServeEngine, "generate", altered)
    assert not _run()["correct"]


def test_moonlight_decode_state_unchanged_fails(monkeypatch):
    from repro.serve.engine import ServeEngine

    orig = ServeEngine.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        step = self._decode_jit
        self._decode_jit = lambda p, b, cache: (step(p, b, cache)[0], cache)

    monkeypatch.setattr(ServeEngine, "__init__", init)
    assert not _run()["correct"]


def test_moonlight_dropped_routes_fail(monkeypatch):
    """The program's expert layer with a capacity of 1.25 per expert, as
    Switch/GShard-style layers have, in place of dropless dispatch.  At
    this size the seeded router rarely overfills an expert, so the
    selection bias of the held experts is raised by 1 (program and
    reference alike): every token then routes to held experts, and the
    capped layer drops most of those routes."""
    drv = R.load_module(R.BENCH / "drivers" / "serve_mla_moe.py")
    ref = R.load_module(R.BENCH / "reference" / "mla_moe_lm.py")
    orig, make = drv.arch_config, ref.make_params

    def capped(c):
        cfg = orig(c)
        return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.25))

    def skewed(c, seed):
        p = make(c, seed)
        moe = p["blocks"][0]["moe"]
        held = slice(c["first_held_expert"], c["first_held_expert"] + c["n_routed_experts"])
        moe["bias"] = moe["bias"].at[:, held].add(1.0)
        return p

    monkeypatch.setattr(drv, "arch_config", capped)
    monkeypatch.setattr(ref, "make_params", skewed)
    out = _run()
    assert not out["correct"], out["checks"]


def test_moonlight_control_fails_a_limit():
    spec, cell, config, traffic = _cell()
    drv_mod = R.load_module(R.BENCH / "drivers" / f"{config['driver']}.py")
    ref = R.load_module(R.BENCH / "reference" / f"{config['reference']}.py")
    drv = drv_mod.Driver(config, traffic, SEED, ref, jax.devices()[:1])
    drv.build()
    drv.window(1.0)
    drv.release()
    limits = config["limits"]
    sound, control = drv.check(), drv.check(control=True)
    assert all(sound[k] <= lim for k, lim in limits.items()), sound
    assert any(control[k] > lim for k, lim in limits.items()), control


@pytest.mark.parametrize("batch", [1, 32])
def test_moonlight_flops_count_every_route_once(batch):
    """At one token the step touches k/E of the held experts in expectation;
    the roofline's bytes never count more than the held experts."""
    import flops_mla_moe as f

    c = R.load_cell(CELL)[2]
    ops1, b1 = f.decode_step(c, batch, 1000, local_routes=0.0)
    ops2, b2 = f.decode_step(c, batch, 1000, local_routes=10.0)
    assert ops2 - ops1 == 2 * 10 * 3 * c["hidden_size"] * c["moe_intermediate_size"]
    assert b1 == b2
    touched = f.experts_touched(c, batch)
    assert 0 < touched <= c["n_routed_experts"]
    if batch == 1:
        assert touched == pytest.approx(c["n_routed_experts"] * 6 / 64)
