"""Traffic driver ``refresh``: rounds refresh back to back, each under a
fixed upload load.  A cycle asks ``RouteServer.refinalize(background=
True)`` for a round (its snapshot is taken at once), uploads
``waves_per_round`` keyed re-upload waves of ``wave_frac`` of the
federation while that round runs, and waits for the round to install.
Waves alternate between ``draws`` seeded draws of every client's upload.
Ingest and the round's cluster-and-mean do the work, concurrently; route
is bypassed.

Every cycle does the same work, so the split of the chip and the host
between uploads and rounds is fixed by the mix, not by which thread wins
a race.  A cycle in flight when the window's time is up runs to its end,
and the rates are taken over every cycle and all of the time up to the
last one's end.

Mix keys: ``wave_frac``, ``draws``, ``waves_per_round``, ``warm_rounds``
(cycles the set-up runs, the window's own loop, so that every program it
runs has run)."""
from __future__ import annotations

import time

import numpy as np

from bench.generator import GRACE_S, Federation, annotate


class Refresh:
    def __init__(self, fed: Federation, mix: dict):
        self.fed, self.mix = fed, mix
        self.waves_per_round = int(mix["waves_per_round"])

    def setup(self) -> None:
        t0 = time.monotonic()
        self.fed.make_draws(int(self.mix["draws"]))
        t1 = time.monotonic()
        self.fed.load()
        t2 = time.monotonic()
        for _ in range(int(self.mix["warm_rounds"])):
            self.cycle()
        self.fed.reset_rounds()
        self.setup_phases = {"draws_s": t1 - t0, "load_s": t2 - t1,
                             "warm_s": time.monotonic() - t2}

    def cycle(self) -> tuple[float, float]:
        """One round under ``waves_per_round`` waves: (start, end)."""
        fed = self.fed
        t0 = time.monotonic()
        clock_req = fed.session.clock
        with annotate("bench.refinalize"):
            pending = fed.server.refinalize(background=True)
        for _ in range(self.waves_per_round):
            fed.ingest_next()
        pending.result(GRACE_S)
        fed.record_round(clock_req)
        return t0, time.monotonic()

    def window(self, seconds: float) -> dict:
        start = time.monotonic()
        cycles, errors = [], []
        while not errors and (not cycles or cycles[-1][1] - start < seconds):
            try:
                cycles.append(self.cycle())
            except Exception as exc:  # noqa: BLE001 - reported as failed
                errors.append(exc)
        elapsed = (cycles[-1][1] if cycles else time.monotonic()) - start
        n = len(cycles)
        waves = n * self.waves_per_round
        took = np.array([b - a for a, b in cycles]) if cycles else None
        # the clients ingested per second are waves_per_round x wave /
        # round_s here, so round_s alone carries the cycle's rate
        metrics = {"round_s": elapsed / n if n else float(elapsed)}
        notes = {"rounds_in_window": n, "waves_in_window": waves,
                 "elapsed_s": elapsed, "setup_phases": self.setup_phases}
        if took is not None:
            notes["round_s_p10_p50_p90"] = [
                float(np.percentile(took, q)) for q in (10, 50, 90)]
        return {"metrics": metrics,
                "attempted": waves + n + len(errors),
                "failed": len(errors),
                "errors": [repr(e) for e in errors],
                "notes": notes}

    def output(self) -> dict:
        # one more wave through the same path, so that the sampled
        # sketches always hold rows whose upload changed at their last
        # write: after a whole number of cycles every block holds the
        # draw it was loaded with, which a dropped write would leave too
        self.fed.ingest_next()
        return self.fed.output(self.fed.rounds)


def make(config: dict, mix: dict, seed: int) -> Refresh:
    return Refresh(Federation(config, seed, mix), mix)
