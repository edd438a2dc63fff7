"""Host time of the uploads inside ``HostKVStore.fetch`` per hit batch:
the program's ``serve.kv.fetch.h2d`` spans, summed (trace).  Nothing to
read without hits or without those spans."""


def read(run):
    sp = run.load("spans.py")
    copies = sp.named(run.view, "serve.kv.fetch.h2d")
    return sp.per_batch_ms(run, True, sp.length_ns(copies)) if copies else None
