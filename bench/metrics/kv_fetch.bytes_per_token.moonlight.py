"""Bytes the host KV fetch moves per context token, from the program's
counters (``kv_fetch.bytes_per_token`` in the latent-attention cell): the
latent and the rotated key of every layer, 27 x 576 x 2 = 31,104 B."""


def read(run):
    return run.load("metrics/kv_fetch.bytes_per_token.py").read(run)
