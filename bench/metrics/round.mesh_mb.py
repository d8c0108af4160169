"""Megabytes (1e6 bytes) moved between the chips of a mesh for each
round installed in the window: the obs counter ``session.mesh.bytes``
(a round's sketch rows gathered to the first chip, plus its labels and
centres copied back to every chip, recorded only by a session on a
mesh) over the rounds.  A program without that counter reports
nothing."""


def read(run):
    moved = run.obs["counters"].get("session.mesh.bytes")
    if moved is None or not run.rounds:
        return None
    return moved / len(run.rounds) / 1e6
