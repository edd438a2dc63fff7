"""Operations and bytes of one decode step of a DeepSeek-V3-style
configuration held as one chip's share of expert parallelism (multi-head
latent attention decoded over the latent, leading dense layers, routed and
shared experts), from the configuration file's keys.

Like ``flops.py`` these count what the algorithm needs: a roofline share
built on them cannot pass 100% unless the time leaves out work.
"""
from __future__ import annotations

BF16 = 2


def attention_params(c: dict) -> int:
    """One layer's latent attention weights: Wq, Wkva, the latent norm,
    Wkvb (W_uk and W_uv, absorbed in decode) and Wo."""
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return d * h * (nope + rope) + d * (r + rope) + r + r * h * (nope + v) + h * v * d


def expert_params(c: dict) -> int:
    """One routed expert's gated MLP."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def experts_touched(c: dict, batch: int) -> float:
    """Expected held experts of a MoE layer that ``batch`` tokens route to,
    each choosing ``num_experts_per_tok`` distinct ones of ``router_experts``
    uniformly: n_held * (1 - (1 - k/E)^batch)."""
    k, E = c["num_experts_per_tok"], c["router_experts"]
    return c["n_routed_experts"] * (1 - (1 - k / E) ** batch)


def decode_step(c: dict, batch: int, live: int, local_routes: float) -> tuple[float, float]:
    """(operations, bytes) of one decode step: ``batch`` sequences, each
    attending over ``live`` cached positions, with ``local_routes`` routes
    to the held experts summed over the MoE layers.

    Operations: 2 per multiply-add of every weight a token multiplies by
    (attention, the dense MLP, router, shared experts, head), of the held
    experts once per route to them, and of the absorbed attention over the
    latent (scores over latent and rotated key, output over the latent).
    Bytes: every weight but the routed experts once, the routed experts the
    batch is expected to touch, the embedding rows looked up, and the live
    latent read plus the one position written, all bf16."""
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    Ld = c["first_k_dense_replace"]
    Lm = L - Ld
    h, r, rope = c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    attn = attention_params(c)
    norms = 2 * d
    dense = 3 * d * c["intermediate_size"]
    shared = c["n_shared_experts"] * expert_params(c)
    router = d * c["router_experts"]
    per_token = L * attn + Ld * dense + Lm * (router + shared) + d * V
    flops = (2 * batch * per_token + 2 * local_routes * expert_params(c)
             + L * 2 * batch * h * (2 * r + rope) * live)
    weights = (L * (attn + norms) + Ld * dense + Lm * (router + c["router_experts"] + shared)
               + Lm * experts_touched(c, batch) * expert_params(c) + d + d * V)
    lookup = batch * d
    latent = L * batch * (r + rope) * (live + 1)
    return float(flops), float((weights + lookup + latent) * BF16)
