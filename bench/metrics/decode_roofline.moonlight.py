"""The decode step's least time over its device time (trace), in percent.

Least time = max(operations / bf16 peak, bytes / HBM peak), from
``bench/flops_mla_moe.py`` at the batch and the mean live context of the
window's decode steps: the held weights, the routed experts counted by the
number a batch is expected to touch (8 (1 - (58/64)^32) at 32 tokens), and
the live latent.  Routes to held experts at their expected share, k/E of
the routes (the bytes bind)."""


def read(run):
    step = run.load("metrics/decode_step_ms.py").durations(run)
    if not step:
        return None
    c = run.config
    f = run.load("flops_mla_moe.py")
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    local = moe_layers * run.batch * c["num_experts_per_tok"] * c["n_routed_experts"] / c["router_experts"]
    ops, nbytes = f.decode_step(c, run.batch, run.prompt_tokens + run.new_tokens // 2, local)
    least = max(ops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return least / (sum(step) / len(step) / 1e9) * 100
