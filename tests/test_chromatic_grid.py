"""The chromatic engine pads each color's kernel grid (``csr_steps``) to
``steps_bound``, a length that depends on the color's edge count and the
vertex count alone.  The exact schedule is shorter by every row-block
boundary that falls on an edge-block boundary, which depends on the
vertex labels: without the padding, two labellings of one graph could
compile two step programs (and miss the compile cache)."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.chromatic import steps_bound
from repro.kernels.gas.gas import EDGE_BLOCK, ROW_BLOCK, csr_steps


def receivers(kind, rng):
    n = int(rng.integers(1, 3000))
    e = int(rng.integers(0, 6000))
    if kind == "uniform":
        r = rng.integers(0, n, e)
    elif kind == "skewed":
        r = np.minimum((rng.pareto(1.1, e) * 3).astype(np.int64), n - 1)
    else:
        # 8 edges a vertex, or none: row blocks of 128 vertices end on
        # edge-block boundaries of 1024 edges
        r = np.repeat(np.arange(n), rng.integers(0, 2, n) * 8)
    return n, np.sort(r)


@pytest.mark.parametrize("kind", ["uniform", "skewed", "aligned"])
def test_bound_holds_and_is_tight_without_coincidences(kind):
    rng = np.random.default_rng(0)
    for _ in range(200):
        n, r = receivers(kind, rng)
        e = r.size
        nb = max(-(-e // EDGE_BLOCK), 1) * int(rng.choice([1, 2, 4]))
        padded = np.concatenate(
            [r, np.full(nb * EDGE_BLOCK - e, n + ROW_BLOCK)])
        steps = csr_steps(padded, n)[0].size
        bound = steps_bound(e, n)
        assert steps <= bound
        # every step the schedule saves is a row-block boundary that
        # falls on an edge-block boundary
        starts = np.searchsorted(r, np.arange(1, -(-n // ROW_BLOCK))
                                 * ROW_BLOCK)
        cuts = np.unique(starts[(starts > 0) & (starts < e)])
        assert bound - steps == int((cuts % EDGE_BLOCK == 0).sum())
