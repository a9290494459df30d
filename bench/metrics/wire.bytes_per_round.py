"""Bytes the server's transport received and sent per round over the
window: ``TransportServer.stats()`` bytes in plus bytes out, the
difference across the window, over the rounds."""


def read(run):
    if not run.round_walls or run.wire_bytes <= 0:
        return None
    return run.wire_bytes / len(run.round_walls)
