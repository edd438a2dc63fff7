"""Drive the serving engine (``repro.serve.engine.ServeEngine``) with a
closed loop of static batches, and check what it served against the plain
reference.

Set-up builds the engine on seeded weights and serves one batch of fresh
contexts as a miss (which compiles prefill, save and decode).  It saves the
rest of the traffic's pool of contexts to the host KV store through the
engine's first-token path alone, with no decode, and serves one pool batch
as a hit where the traffic has hits (which compiles the fetch and cache
rebuild).  The window then serves batches back to back until the time is
up; each is a hit on a pool batch or a miss on fresh contexts that join the
pool, in the order the traffic file's cycle and the seed give.  Hits go
through the pool in a seeded order, each pool batch once before any twice.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import jax

from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.serve.engine import ServeEngine
from repro.serve.host_store import HostKVStore


def arch_config(c: dict) -> ArchConfig:
    """The program's config object for a configuration file."""
    return ArchConfig(
        name=c["name"], family="dense", source=c["source"],
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], head_dim=c["head_dim"],
        qkv_bias=c["qkv_bias"], tie_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]), compute_dtype=c["compute_dtype"],
        param_dtype=c["param_dtype"])


class TimedHostKVStore(HostKVStore):
    """The program's host store, with the host time spent in ``fetch``
    summed and each call marked in the profiler's trace."""

    def __init__(self):
        super().__init__()
        self.fetch_s = 0.0

    def fetch(self, key, backend="b2b"):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.kv_fetch"):
            res = super().fetch(key, backend)
        self.fetch_s += time.perf_counter() - t0
        return res


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, reference, devices):
        self.c, self.t, self.seed, self.ref = config, traffic, seed, reference
        self.rng = np.random.default_rng([seed, 1])
        self.B, self.S, self.new = traffic["batch"], traffic["prompt_tokens"], traffic["new_tokens"]
        self.batches: list[dict] = []
        self.prompts: list[np.ndarray] = []      # by context batch id
        self.pool: list[int] = []                # ids of batches saved on the host

    # -------------------------------------------------------------- set-up
    def _fresh(self) -> int:
        self.prompts.append(self.rng.integers(0, self.c["vocab_size"], (self.B, self.S),
                                              dtype=np.int32))
        return len(self.prompts) - 1

    def _keys(self, bid: int) -> list[str]:
        return [f"ctx{bid}-{i}" for i in range(self.B)]

    def _serve(self, bid: int, hit: bool) -> dict:
        f0 = self.store.fetch_s
        with jax.profiler.TraceAnnotation("bench.batch.hit" if hit else "bench.batch.miss"):
            res = self.engine.generate(self.prompts[bid], self._keys(bid), self.new)
        if res.request_stats[0].cache_hit != hit:
            raise RuntimeError(f"batch {bid} served as {'miss' if hit else 'hit'}")
        return {"bid": bid, "hit": hit,
                "ttft_s": res.request_stats[0].ttft_wall_s,
                "decode_s": res.decode_wall_s, "fetch_s": self.store.fetch_s - f0,
                "tokens": res.tokens}

    def _save(self, bid: int) -> None:
        """Prefill a batch of fresh contexts and save it to the host store,
        at the cache capacity that ``generate`` uses, without decoding."""
        with jax.profiler.TraceAnnotation("bench.pool"):
            _, _, cache, stats = self.engine.first_token(
                self.prompts[bid], self._keys(bid), capacity=self.S + self.new + 1)
        if stats[0].cache_hit:
            raise RuntimeError(f"batch {bid} served as a hit")
        del cache

    def build(self) -> None:
        self.model = build_model(arch_config(self.c))
        self.params = self.ref.make_params(self.c, self.seed)
        self.store = TimedHostKVStore()
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

    def _next_kind(self) -> str:
        if not self._next:                       # each cycle in an order of its own
            self._next = [str(k) for k in self._order.permutation(self.t["cycle"])]
        return self._next.pop()

    def _next_hit(self) -> int:
        if not self._reask:                      # the whole pool, then again
            self._reask = [self.pool[i] for i in self._order.permutation(len(self.pool))]
        return self._reask.pop()

    # -------------------------------------------------------------- window
    def window(self, seconds: float) -> None:
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            if self._next_kind() == "hit":
                self.batches.append(self._serve(self._next_hit(), hit=True))
            else:
                bid = self._fresh()
                self.batches.append(self._serve(bid, hit=False))
                self.pool.append(bid)

    def records(self) -> dict:
        reqs = []
        for b in self.batches:
            for _ in range(self.B):
                reqs.append({"hit": b["hit"], "ttft_s": b["ttft_s"],
                             "tpot_s": b["decode_s"] / (self.new - 1), "tokens": self.new})
        return {"requests": reqs, "batches": self.batches, "batch": self.B,
                "attempted": len(reqs), "failed": 0,
                "prompt_tokens": self.S, "new_tokens": self.new}

    def release(self) -> None:
        del self.engine, self.params, self.store, self.model
        gc.collect()

    # --------------------------------------------------------------- check
    def sample(self) -> list[tuple[int, int]]:
        """(batch index, row) of the requests to check: drawn from the seed,
        half from hits and half from misses where the window had both."""
        n = self.t["sample_requests"]
        rng = np.random.default_rng([self.seed, 3])
        by_kind = {k: [(i, r) for i, b in enumerate(self.batches) if b["hit"] == k
                       for r in range(self.B)] for k in (True, False)}
        kinds = [k for k in (True, False) if by_kind[k]]
        picked = []
        for j, k in enumerate(kinds):
            share = n // len(kinds) + (1 if j < n % len(kinds) else 0)
            pool = by_kind[k]
            idx = rng.choice(len(pool), size=min(share, len(pool)), replace=False)
            picked += [pool[i] for i in sorted(idx)]
        return picked

    def check(self, *, control: bool = False) -> dict:
        """Widest gap between a served token's logit and the reference's
        best, over the sample.  Call after ``release``."""
        picked = self.sample()
        prompts = np.stack([self.prompts[self.batches[i]["bid"]][r] for i, r in picked])
        served = np.stack([self.batches[i]["tokens"][r] for i, r in picked])
        params = self.ref.make_params(self.c, self.seed)
        gaps = self.ref.served_gaps(self.c, params, prompts, served, control=control)
        del params
        return {"logit_gap": float(gaps.max()), "checked_tokens": int(gaps.size)}
