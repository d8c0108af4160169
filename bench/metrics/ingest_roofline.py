"""Share of its roofline that the session's ingest program reaches: the
least time of one wave (``costs.ingest_wave``: read the wave, write its
rows and sketches, a dense JL projection) times the waves the trace
holds, over the program's device time."""
from bench import costs

MODULE = "jit__ingest"


def read(run):
    if run.trace is None:
        return None
    mod = run.trace["modules"].get(MODULE)
    if not mod or mod["n"] == 0:
        return None
    wave = int(round(run.mix["wave_frac"]
                     * int(run.config["federation"]["clients"])))
    width = sum(_size(s) for s in run.config["upload"].values())
    work = costs.ingest_wave(wave, width, int(run.config["session"]
                                              ["sketch_dim"]))
    return costs.roofline_pct([work] * mod["n"], mod["s"], run.peak)


def _size(shape):
    n = 1
    for x in shape:
        n *= int(x)
    return n
