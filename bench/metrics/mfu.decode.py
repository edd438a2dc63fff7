"""Model operations of the window's decode steps over (their host-clock
time x the chip's bf16 peak), in percent.  The time is the engine's decode
wall time, host gaps included, so the share bounds what any change to the
step, kernels or host code can win."""


def read(run):
    ops, _ = run.flops.dense_decode_step(run.config, run.batch,
                                         run.prompt_tokens + run.new_tokens // 2)
    steps = len(run.batches) * (run.new_tokens - 1)
    wall = sum(b["decode_s"] for b in run.batches)
    return ops * steps / wall / run.peaks["bf16_flops_per_s"] * 100
