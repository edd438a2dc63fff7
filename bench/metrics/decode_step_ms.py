"""Device time per execution of the engine's decode-step program
(``jit_decode_step``) on the first chip, from the trace."""

PROGRAM = r"decode_step"


def durations(run):
    v = run.view
    return [e - s for _, s, e in v.reduce.modules_named(v.trace, v.devs[0], PROGRAM, v.lo, v.hi)]


def read(run):
    d = durations(run)
    return sum(d) / len(d) / 1e6 if d else None
