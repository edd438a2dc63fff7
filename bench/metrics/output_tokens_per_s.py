"""Output tokens of every request completed in the window, over the window
(host clock)."""


def read(run):
    return sum(r["tokens"] for r in run.requests) / run.window_s
