"""Mean Lloyd iterations of the rounds installed in the window (the
device meta ``n_iter`` of each ``kmeans-device`` round)."""


def read(run):
    if run.config["finalize"]["algorithm"] != "kmeans-device":
        return None
    iters = [r["n_iter"] for r in run.rounds if r.get("n_iter") is not None]
    return float(sum(iters)) / len(iters) if iters else None
