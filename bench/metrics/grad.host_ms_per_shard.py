"""Host time of one client gradient: the benchmark's ``grad`` spans
around the program's task over the window, summed, over the shards.
Holds the images' copy to the device, the dispatch, the device's work
and the ``device_get`` of the gradients."""


def read(run):
    spans = run.spans.get("grad", [])
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
