"""Device time per execution of the engine's prefill program
(``jit_prefill``) on the first chip, from the trace."""

PROGRAM = r"^jit_prefill\b"


def read(run):
    v = run.view
    d = [e - s for _, s, e in v.reduce.modules_named(v.trace, v.devs[0], PROGRAM, v.lo, v.hi)]
    return sum(d) / len(d) / 1e6 if d else None
