"""Device time of one client gradient: the summed device time of the
executions, in the traced window, of the program that runs within the
benchmark's ``grad`` spans, over the window's shards."""


def read(run):
    if run.trace is None or run.shards <= 0:
        return None
    execs = run.trace.programs("grad")
    if not execs:
        return None
    return 1e3 * sum(m.seconds for m in execs) / run.shards
