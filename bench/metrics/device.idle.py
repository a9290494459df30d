"""Share of the window in which no operation ran on the chip, from the
profiler's trace: 100 x (1 - busy / window), busy being the union of the
device's operation intervals, averaged over the chips used."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
