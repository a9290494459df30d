"""Host time of a round that no benchmark span covers: the mean round
wall time less the server step and the client gradients of the round.
What is left is the round engine's publish and barrier, the ticket
queue, the wire and the event loop."""


def read(run):
    walls = run.round_walls
    if not walls:
        return None
    covered = sum(run.spans.get("server_step", [])) + sum(
        run.spans.get("grad", []))
    return 1e3 * (sum(walls) - covered) / len(walls)
