"""Time per miss batch that pulling the prefill's K/V to the host adds
after the prefill program has finished on the chip: the part of each
``serve.kv.pull`` span after the end of the ``jit_prefill`` execution
before it (host and device clocks of one trace)."""


def read(run):
    sp = run.load("spans.py")
    pulls = sp.named(run.view, "serve.kv.pull")
    if not pulls:
        return None
    return sp.per_batch_ms(run, False,
                           sp.after_program_ns(run.view, pulls, run.load("metrics/prefill_ms.py").PROGRAM))
