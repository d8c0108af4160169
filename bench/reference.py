"""The plain reference of a planted federation, and the comparison that
decides ``correct``.

It imports nothing of the program.  From the uploads the traffic sent
it computes, in float64 on the host:

* each client's JL sketch, by the projection the session states: the
  upload's leaves flattened in key order, padded to whole blocks of
  ``block`` floats, each block times its own N(0, 1) matrix drawn from
  ``fold_in(PRNGKey(seed), block index)``, the sum over blocks divided
  by sqrt(sketch_dim);
* each cluster's mean model: the float64 mean of its members' uploads,
  as they stood at the clock of the snapshot the round was built from;
* the planted partition, against which a round's labels are matched,
  and the cluster a fresh client belongs to.

The control puts this reference in the program's place one precision
step below what the configuration states: sketches from float8 (e4m3)
operands where the session projects with bfloat16 operands, means over
bfloat16 uploads where the session averages in float32.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
from scipy.optimize import linear_sum_assignment

# ---------------------------------------------------------------- schedule


class Schedule:
    """Which draw of the federation each client holds at each clock.

    Wave g (the session's clock runs 1, 2, ... as waves commit) uploads
    block ``g % blocks`` of ``wave`` consecutive clients from draw
    ``(g // blocks) % draws``: the set-up's first pass loads draw 0."""

    def __init__(self, clients: int, wave: int, draws: int):
        if clients % wave:
            raise ValueError(f"wave {wave} does not divide {clients}")
        self.clients, self.wave, self.draws = clients, wave, draws
        self.blocks = clients // wave

    def wave_rows(self, g: int) -> tuple[int, int]:
        b = g % self.blocks
        return b * self.wave, (b + 1) * self.wave

    def wave_draw(self, g: int) -> int:
        return (g // self.blocks) % self.draws

    def block_draws(self, clock: int) -> np.ndarray:
        """(blocks,) draw each block holds once ``clock`` waves have
        committed (every block has been loaded by then)."""
        if clock < self.blocks:
            raise ValueError(f"clock {clock} is before the first full pass")
        b = np.arange(self.blocks)
        last = b + self.blocks * ((clock - 1 - b) // self.blocks)
        return (last // self.blocks) % self.draws

    def row_draws(self, clock: int) -> np.ndarray:
        return np.repeat(self.block_draws(clock), self.wave)


# ---------------------------------------------------------------- sketches


def _flatten(uploads: dict, rows) -> np.ndarray:
    return np.concatenate([uploads[k][rows].reshape(len(rows), -1)
                           for k in sorted(uploads)], axis=1)


def jl_block(n: int) -> int:
    return max(256, min(1 << 16, -(-n // 256) * 256))


def projection(seed: int, n: int, sketch_dim: int) -> list:
    """The session's JL matrix, block by block, as float32 host arrays."""
    import jax

    block = jl_block(n)
    key = jax.random.PRNGKey(seed)
    return [np.asarray(jax.random.normal(jax.random.fold_in(key, i),
                                         (block, sketch_dim)))
            for i in range(-(-n // block))]


def jl_sketch(vec: np.ndarray, blocks: list, sketch_dim: int,
              operand=None) -> np.ndarray:
    """(r, n) flat uploads -> (r, sketch_dim) sketches, in float64;
    ``operand`` rounds both operands to that dtype first (the control)."""
    block = blocks[0].shape[0]
    acc = np.zeros((vec.shape[0], sketch_dim))
    for i, s in enumerate(blocks):
        a = vec[:, i * block:(i + 1) * block]
        s = s[:a.shape[1]]
        if operand is not None:
            a = a.astype(operand).astype(np.float32)
            s = s.astype(operand).astype(np.float32)
        acc += a.astype(np.float64) @ s.astype(np.float64)
    return acc / np.sqrt(sketch_dim)


# ------------------------------------------------------------------ means


class ClusterSums:
    """Per (draw, block, planted cluster) sums of the uploads, so that the
    mean of every cluster at any clock is a sum of ``blocks`` rows."""

    def __init__(self, draws: list, truth: np.ndarray, schedule: Schedule,
                 clusters: int, operand=None):
        self.truth, self.schedule, self.clusters = truth, schedule, clusters
        self.keys = sorted(draws[0])
        w = schedule.wave
        self.sums = {}
        for k in self.keys:
            shape = draws[0][k].shape[1:]
            out = np.zeros((len(draws), schedule.blocks, clusters) + shape)
            for d, draw in enumerate(draws):
                x = draw[k]
                if operand is not None:
                    x = x.astype(operand).astype(np.float32)
                for b in range(schedule.blocks):
                    lab = truth[b * w:(b + 1) * w]
                    xb = x[b * w:(b + 1) * w]
                    for t in range(clusters):
                        # float32 accumulation for the control, float64
                        # for the reference
                        out[d, b, t] = xb[lab == t].sum(
                            axis=0,
                            dtype=np.float32 if operand is not None
                            else np.float64)
            self.sums[k] = out
        self.counts = np.bincount(truth, minlength=clusters)

    def means(self, clock: int) -> dict:
        """{leaf: (clusters, ...)} planted-cluster means at ``clock``."""
        draws = self.schedule.block_draws(clock)
        blocks = np.arange(self.schedule.blocks)
        out = {}
        for k in self.keys:
            total = self.sums[k][draws, blocks].sum(axis=0)
            out[k] = total / self.counts.reshape(
                (-1,) + (1,) * (total.ndim - 1))
        return out


# -------------------------------------------------------------- partition


def match(found: np.ndarray, truth: np.ndarray) -> tuple[dict, int]:
    """Best one-to-one map planted label -> found label, and the number
    of clients it leaves off their planted cluster's image."""
    found = np.asarray(found)
    truth = np.asarray(truth)
    f_ids = np.unique(found)
    t_ids = np.unique(truth)
    table = np.zeros((len(t_ids), len(f_ids)), np.int64)
    np.add.at(table, (np.searchsorted(t_ids, truth),
                      np.searchsorted(f_ids, found)), 1)
    rows, cols = linear_sum_assignment(-table)
    mapping = {int(t_ids[r]): int(f_ids[c]) for r, c in zip(rows, cols)}
    return mapping, int(len(found) - table[rows, cols].sum())


# ------------------------------------------------------------- comparison


def compare(record: dict, ref: dict, *, control: bool = False) -> dict:
    """The numbers compared, from what the timed path produced
    (``record``) and the federation's uploads (``ref``).

    ``record``: ``truth`` (m,), ``rounds`` (each with ``clock``,
    ``clock_req``, compact ``labels`` (m,) and ``models`` {leaf: (K',
    ...)}, or None for a round outside the seeded sample whose models
    were kept), and ``sketch_rows`` and ``sketches`` at
    ``sketch_clock``.
    ``ref``: ``draws``, ``schedule``, ``seed``, ``sketch_dim``,
    ``clusters``.  With ``control`` the reference computed one precision
    step below replaces the program's sketches and models.
    """
    truth = record["truth"]
    schedule = ref["schedule"]
    draws = ref["draws"]
    k = ref["clusters"]
    out = {}

    # sketches of the live clients, as they stood at sketch_clock
    rows = np.asarray(record["sketch_rows"])
    held = schedule.row_draws(record["sketch_clock"])[rows]
    vec = np.empty((len(rows), sum(int(np.prod(v.shape[1:]))
                                   for v in draws[0].values())), np.float32)
    for d in range(len(draws)):
        pick = held == d
        if pick.any():
            vec[pick] = _flatten(draws[d], rows[pick])
    blocks = projection(ref["seed"], vec.shape[1], ref["sketch_dim"])
    want = jl_sketch(vec, blocks, ref["sketch_dim"])
    got = (jl_sketch(vec, blocks, ref["sketch_dim"],
                     operand=ml_dtypes.float8_e4m3fn)
           if control else np.asarray(record["sketches"], np.float64))
    out["sketch_rel_err"] = float(np.max(
        np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)))

    # every recorded round: its partition and its served models
    sums = ClusterSums(draws, truth, schedule, k)
    ctrl = (ClusterSums(draws, truth, schedule, k, operand=ml_dtypes.bfloat16)
            if control else None)
    part_err, model_err, stale, models_checked = 0, 0.0, 0, 0
    for rnd in record["rounds"]:
        stale += int(rnd["clock"] < rnd["clock_req"])
        mapping, errs = ((({t: t for t in range(k)}), 0) if control
                         else match(rnd["labels"], truth))
        part_err += errs
        if rnd["models"] is None and not control:
            continue                  # a round outside the model sample
        models_checked += 1
        want = sums.means(rnd["clock"])
        have = ctrl.means(rnd["clock"]) if control else None
        for t, f in mapping.items():
            for leaf, mean in want.items():
                got = (have[leaf][t] if control
                       else np.asarray(rnd["models"][leaf][f], np.float64))
                model_err = max(model_err,
                                float(np.max(np.abs(got - mean[t]))))
    out["partition_errors"] = part_err
    out["model_err"] = model_err
    out["stale_rounds"] = stale
    out["rounds_checked"] = len(record["rounds"])
    out["model_rounds_checked"] = models_checked

    return out


def checks(numbers: dict, limits: dict) -> list:
    """[(name, value, op, limit, ok)] for every number that has a limit:
    each is at most its limit, but for the counts of what was checked
    (``*_checked``), of which a run must hold at least its limit."""
    out = []
    for name, limit in limits.items():
        if name not in numbers:
            continue
        value = numbers[name]
        op = ">=" if name.endswith("_checked") else "<="
        ok = value >= limit if op == ">=" else value <= limit
        out.append((name, value, op, limit, bool(ok)))
    return out
