"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see the
single real CPU device; only launch/dryrun.py forces 512 host devices."""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def same_partition(a, b) -> bool:
    """Label vectors agree up to renaming of cluster ids (shared by the
    engine, engine-property, and federated-method tests)."""
    a, b = np.asarray(a), np.asarray(b)
    fwd, bwd = {}, {}
    for x, y in zip(a, b):
        if fwd.setdefault(x, y) != y or bwd.setdefault(y, x) != x:
            return False
    return True


def gathered_rows(hlo: str, row_shapes) -> list:
    """The client rows each all-gather or all-to-all of a compiled
    program's text produces: per result array, the product of the dims
    in front of the longest trailing run that is one client's row of a
    gathered array (a leaf's ``shape[1:]``, a sketch row, or ``()``)."""
    import math
    import re

    rows = []
    suffixes = sorted({tuple(s) for s in row_shapes} | {()},
                      key=len, reverse=True)
    for result in re.findall(
            r"= (.+?) (?:all-gather|all-to-all)(?:-start|-done)?\(", hlo):
        for dims in re.findall(r"\b[a-z]+\d*\[([\d,]*)\]", result):
            dims = tuple(int(d) for d in dims.split(",") if d)
            row = next((s for s in suffixes if len(s) < len(dims)
                        and dims[len(dims) - len(s):] == s), ())
            rows.append(math.prod(dims[:len(dims) - len(row)]))
    return rows
