"""Time per decode step in which the chip waits for the host: the time
inside the program's ``serve.decode`` spans in which no program runs on
the first chip, over the window's decode steps (trace).  With
``decode_step_ms`` and the small argmax program it makes up a traced
decode step."""


def read(run):
    sp = run.load("spans.py")
    decode = sp.named(run.view, "serve.decode")
    if not decode:
        return None
    steps = len(run.batches) * (run.new_tokens - 1)
    return sp.idle_ns(run.view, decode) / steps / 1e6
