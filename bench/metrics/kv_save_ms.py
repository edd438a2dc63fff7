"""Host time a miss spends after its first token to keep its K/V, per
miss batch: the program's ``serve.kv.save`` spans (blocking and storing
each context) and the ``serve.cache.build`` spans outside
``serve.first_token`` (trace)."""


def read(run):
    sp = run.load("spans.py")
    builds = sp.outside(sp.named(run.view, "serve.cache.build"),
                        sp.named(run.view, "serve.first_token"))
    parts = sp.named(run.view, "serve.kv.save") + builds
    return sp.per_batch_ms(run, False, sp.length_ns(parts)) if parts else None
