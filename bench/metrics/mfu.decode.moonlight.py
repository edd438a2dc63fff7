"""Model operations of the window's decode steps over (their host-clock
time x the chip's bf16 peak), in percent.  Operations from
``bench/flops_mla_moe.py``; those of the routed experts from the routes
that reached the held experts (``moe.routes.local`` over ``moe.routes``,
the program's counters, times the window's routes).  Nothing to read
without those counters."""


def read(run):
    try:
        from repro.serve.counters import counters
    except ImportError:
        return None
    cn = counters()
    if not cn.get("moe.routes"):
        return None
    c = run.config
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    routes = moe_layers * run.batch * c["num_experts_per_tok"]
    local = routes * cn["moe.routes.local"] / cn["moe.routes"]
    ops, _ = run.load("flops_mla_moe.py").decode_step(
        c, run.batch, run.prompt_tokens + run.new_tokens // 2, local)
    steps = len(run.batches) * (run.new_tokens - 1)
    wall = sum(b["decode_s"] for b in run.batches)
    return ops * steps / wall / run.peaks["bf16_flops_per_s"] * 100
