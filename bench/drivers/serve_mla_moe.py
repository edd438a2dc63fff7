"""The serving driver of ``serve.py`` for a DeepSeek-V3-style configuration
(multi-head latent attention, leading dense layers, sigmoid-routed experts
with shared experts) held as one chip's share of expert parallelism.

The served path is the same ``ServeEngine`` and host store.  The build
differs: the configuration file's keys (the model's own ``config.json``
names) become the program's ``ArchConfig``, with the experts this chip
holds (``n_routed_experts`` from ``first_held_expert``) beside the
router's published width (``router_experts``).  And the check adds the
mean gap to the widest: one altered token widens the widest gap, while a
lower precision, dropped routes or a stale cache move the mean over every
checked token (PERF.md, section 2).
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig
from repro.models import build_model
from repro.serve.engine import ServeEngine


def _serve_module():
    """``serve.py`` beside this file, as ``bench/run.py`` names it."""
    name = "bench_drivers_serve_py"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, Path(__file__).with_name("serve.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


serve = _serve_module()


def arch_config(c: dict) -> ArchConfig:
    """The program's config object for a configuration file."""
    return ArchConfig(
        name=c["name"], family="moe", source=c["source"],
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], head_dim=c["v_head_dim"],
        tie_embeddings=c["tie_word_embeddings"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], first_dense_layers=c["first_k_dense_replace"],
        mla=MLAConfig(kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
                      qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"]),
        moe=MoEConfig(n_experts=c["router_experts"], top_k=c["num_experts_per_tok"],
                      d_ff_expert=c["moe_intermediate_size"], capacity_factor=None,
                      scoring=c["scoring_func"], routed_scaling=c["routed_scaling_factor"],
                      n_shared_experts=c["n_shared_experts"],
                      held=(c["first_held_expert"], c["n_routed_experts"])),
        compute_dtype=c["compute_dtype"], param_dtype=c["param_dtype"])


class Driver(serve.Driver):
    def build(self) -> None:
        self.model = build_model(arch_config(self.c))
        self.params = self.ref.make_params(self.c, self.seed)
        self.store = serve.TimedHostKVStore()
        self.engine = ServeEngine(self.model, self.params, host_store=self.store)
        for i in range(self.t["pool_batches"]):
            bid = self._fresh()
            if i == 0:
                self._serve(bid, hit=False)
            else:
                self._save(bid)
            self.pool.append(bid)
        self._order = np.random.default_rng([self.seed, 2])
        self._next: list[str] = []
        self._reask: list[int] = []
        if "hit" in self.t["cycle"]:
            self._serve(self.pool[0], hit=True)

    def check(self, *, control: bool = False) -> dict:
        """The widest and the mean gap between a served token's logit and the
        reference's best, over the sample; and, for calibration, the widest
        gap a run would read with each sampled request's last token altered
        as the checks alter it (shifted by half the vocabulary).  Call after
        ``release``."""
        picked = self.sample()
        prompts = np.stack([self.prompts[self.batches[i]["bid"]][r] for i, r in picked])
        served = np.stack([self.batches[i]["tokens"][r] for i, r in picked])
        params = self.ref.make_params(self.c, self.seed)
        gaps, altered = self.ref.served_gaps(self.c, params, prompts, served, control=control,
                                             shift=self.c["vocab_size"] // 2)
        del params
        return {"logit_gap": float(gaps.max()), "logit_gap_mean": float(gaps.mean()),
                "altered_gap": float(max(gaps[:, :-1].max(), altered.max())),
                "checked_tokens": int(gaps.size)}
