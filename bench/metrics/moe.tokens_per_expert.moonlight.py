"""Tokens each held expert computes per MoE layer and decode step:
``moe.routes.local`` over (decode steps x MoE layers x held experts), with
the steps x layers taken from ``moe.routes`` / (batch x top-k) (the
program's counters, summed on the device).  Nothing to read without them."""


def read(run):
    try:
        from repro.serve.counters import counters
    except ImportError:
        return None
    cn = counters()
    if not cn.get("moe.routes"):
        return None
    c = run.config
    steps_x_layers = cn["moe.routes"] / (run.batch * c["num_experts_per_tok"])
    return cn["moe.routes.local"] / steps_x_layers / c["n_routed_experts"]
