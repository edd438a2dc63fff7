"""Bytes the host KV fetch moves per context token, both ways together:
(``kv.fetch.to_device_bytes`` + ``kv.fetch.to_host_bytes``) over
``kv.fetch.tokens``, from the program's counters (``repro.serve.counters``).
Only the fetch moves these totals, so their ratio over the process is the
window's.  Nothing to read where the program has no such counters or
fetched nothing."""


def read(run):
    try:
        from repro.serve.counters import counters
    except ImportError:
        return None
    c = counters()
    tokens = c.get("kv.fetch.tokens", 0)
    if not tokens:
        return None
    return (c.get("kv.fetch.to_device_bytes", 0) + c.get("kv.fetch.to_host_bytes", 0)) / tokens
