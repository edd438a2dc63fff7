"""The comparison that decides ``correct``, driven through the harness on
the CPU: a sound run passes its cell's limit; the control (the reference
in fp8) and each fault the cell can have, planted in the program under the
timed path, fail it.

The sizes keep every published width and cut depth, vocabulary, prompt
length and batch, so that a run fits a test.  The limits are the cells'
own, from their configuration files."""
from __future__ import annotations

import time

import jax
import pytest

import run as R

SEED = 2**31 + 99


def _run(cell_name: str, config_cuts: dict, traffic_cuts: dict):
    spec, cell, config, traffic = R.load_cell(cell_name)
    R.prepare_imports()
    config = dict(config, **config_cuts)
    traffic = dict(traffic, **traffic_cuts)
    out = R.run_cell(spec, cell, config, traffic, seed=SEED, seconds=1.0, trace=False,
                     devices=jax.devices()[:1], peaks=None, t_start=time.perf_counter())
    return out


# ------------------------------------------------------------------ serving
CUTS = ({"num_hidden_layers": 6},
        {"batch": 2, "prompt_tokens": 48, "new_tokens": 8, "pool_batches": 2,
         "sample_requests": 4})
CELLS = pytest.mark.parametrize("cell", ["serve-hit.qwen2-0.5b", "serve-miss.qwen2-0.5b"])
SERVE = ("serve-hit.qwen2-0.5b",) + CUTS


@CELLS
def test_serve_sound_run_is_correct(cell):
    assert _run(cell, *CUTS)["correct"]


@CELLS
def test_serve_altered_token_fails(monkeypatch, cell):
    from repro.serve.engine import ServeEngine

    orig = ServeEngine.generate

    def altered(self, prompts, keys, n_new, **kw):
        res = orig(self, prompts, keys, n_new, **kw)
        vocab = self.model.cfg.vocab
        res.tokens[:, -1] = (res.tokens[:, -1] + vocab // 2) % vocab
        return res

    monkeypatch.setattr(ServeEngine, "generate", altered)
    assert not _run(cell, *CUTS)["correct"]


@CELLS
def test_serve_decode_state_unchanged_fails(monkeypatch, cell):
    from repro.serve.engine import ServeEngine

    orig = ServeEngine.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        step = self._decode_jit
        self._decode_jit = lambda p, b, cache: (step(p, b, cache)[0], cache)

    monkeypatch.setattr(ServeEngine, "__init__", init)
    assert not _run(cell, *CUTS)["correct"]


def test_serve_control_fails_the_limit():
    spec, cell, config, traffic = R.load_cell(SERVE[0])
    R.prepare_imports()
    config = dict(config, **SERVE[1])
    traffic = dict(traffic, **SERVE[2])
    drv_mod = R.load_module(R.BENCH / "drivers" / "serve.py")
    ref = R.load_module(R.BENCH / "reference" / "dense_lm.py")
    drv = drv_mod.Driver(config, traffic, SEED, ref, jax.devices()[:1])
    drv.build()
    drv.window(1.0)
    drv.release()
    limit = config["limits"]["logit_gap"]
    assert drv.check()["logit_gap"] <= limit < drv.check(control=True)["logit_gap"]
