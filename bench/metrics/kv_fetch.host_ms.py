"""Host time inside ``HostKVStore.fetch`` outside its copies, per hit
batch: the self time of the program's ``serve.kv.fetch`` spans, less their
``serve.kv.fetch.h2d`` and ``serve.kv.fetch.d2h`` children (staging,
slicing, whatever else runs in the fetch; trace)."""


def read(run):
    sp = run.load("spans.py")
    fetches = sp.named(run.view, "serve.kv.fetch")
    if not fetches:
        return None
    copies = sp.named(run.view, "serve.kv.fetch.h2d") + sp.named(run.view, "serve.kv.fetch.d2h")
    return sp.per_batch_ms(run, True, sp.self_ns(run.view, fetches, copies))
