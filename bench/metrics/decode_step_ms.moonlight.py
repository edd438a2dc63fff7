"""Device time per execution of the engine's decode-step program
(``jit_decode_step``) on the first chip, from the trace: ``decode_step_ms``
read in the latent-attention MoE cell."""


def read(run):
    return run.load("metrics/decode_step_ms.py").read(run)
