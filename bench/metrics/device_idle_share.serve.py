"""Share of the traced window in which no operation ran on the chip:
1 - busy union / window, averaged over the chips used (trace)."""


def read(run):
    return (1 - run.view.busy_s / run.view.window_s) * 100
