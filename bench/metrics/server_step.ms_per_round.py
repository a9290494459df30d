"""Host time of the server step per round, ending when the new
parameters are ready on the device: the benchmark's ``server_step``
spans over the window, summed, over the rounds."""


def read(run):
    spans = run.spans.get("server_step", [])
    if not spans or not run.round_walls:
        return None
    return 1e3 * sum(spans) / len(run.round_walls)
