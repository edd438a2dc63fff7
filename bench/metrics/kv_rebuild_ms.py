"""Host time a hit spends turning fetched blocks into the decode cache,
per hit batch: the program's ``serve.kv.unpack`` and ``serve.cache.build``
spans inside ``serve.first_token`` (trace).  A miss builds its cache after
its first token, outside that span."""


def read(run):
    sp = run.load("spans.py")
    first = sp.named(run.view, "serve.first_token")
    parts = sp.within(sp.named(run.view, "serve.kv.unpack")
                      + sp.named(run.view, "serve.cache.build"), first)
    return sp.per_batch_ms(run, True, sp.length_ns(parts)) if parts else None
