"""The decode step's least time over its device time (trace), in percent.

Least time = max(operations / bf16 peak, bytes / HBM peak), from
``bench/flops.py`` at the batch and the mean live context of the window's
decode steps: weights as stored, plus the live K/V, not the masked
capacity.  At these batches the bytes bind."""


def least_s(run):
    live = run.prompt_tokens + run.new_tokens // 2
    ops, nbytes = run.flops.dense_decode_step(run.config, run.batch, live)
    return max(ops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])


def read(run):
    step = run.load("metrics/decode_step_ms.py").durations(run)
    if not step:
        return None
    return least_s(run) / (sum(step) / len(step) / 1e9) * 100
