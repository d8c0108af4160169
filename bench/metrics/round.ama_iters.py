"""Mean AMA iterations of the rounds installed in the window (the device
meta ``n_iter`` of each ``convex-device`` round)."""


def read(run):
    if run.config["finalize"]["algorithm"] != "convex-device":
        return None
    iters = [r["n_iter"] for r in run.rounds if r.get("n_iter") is not None]
    return float(sum(iters)) / len(iters) if iters else None
