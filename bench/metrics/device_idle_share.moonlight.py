"""Share of the traced window in which no operation ran on the chip:
``device_idle_share.serve`` read in the latent-attention MoE cell."""


def read(run):
    return run.load("metrics/device_idle_share.serve.py").read(run)
