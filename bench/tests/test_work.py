"""The work counts on a graph small enough to count by hand, and the
roofline share built on them.

Pairs (0,1), (1,2), (1,3): degrees 1, 3, 1, 1.  Call A updates vertices 0
and 1 once; call B updates vertex 1 twice and vertex 3 once.
  updates        A 2, B 3                         = 5
  updated edges  A 1 + 3, B 2*3 + 1               = 11
  neighbor reads Σ_w max_{v in N(w)} Δ_v:
                 A  w0:1 w1:1 w2:1 w3:1 = 4;  B  w0:2 w1:1 w2:2 w3:2 = 7
                                                  = 11
"""
import numpy as np
import pytest

from bench import run, spec

GRAPH = run.Graph(4, np.array([0, 1, 1, 2, 1, 3]), np.array([1, 0, 2, 1, 3, 1]))
DELTAS = [np.array([1, 1, 0, 0]), np.array([0, 2, 0, 1])]
PAGERANK = {"work": {"gather": {"source_width": 1, "output_width": 1,
                                "edge_bytes": 4, "flops_per_edge": 1}}}
WIDE = {"work": {"gather": {"source_width": 20, "output_width": 20,
                            "edge_bytes": 8, "flops_per_edge": 460}}}


def counts():
    return run.UpdateCounts(GRAPH, DELTAS)


def test_update_counts_by_hand():
    c = counts()
    assert (c.updates(), c.updated_edges(), c.neighbor_reads()) == (5, 11, 11)


def test_gather_edge_major():
    w = spec.module("work", "gas_gather_edge_major").work
    assert w(counts(), PAGERANK) == (11, 4 * 11 + 4 * 11 + 4 * 5)
    assert w(counts(), WIDE) is None


def test_scatter():
    w = spec.module("work", "gas_scatter").work
    assert w(counts(), PAGERANK) == (11, 4 * 11 + 4 * 5 + 8 * 11)


class FakeRun:
    def __init__(self, seconds):
        self.seconds = seconds
        self.cell = type("C", (), {"config": PAGERANK})()

    def kernel_seconds(self, pattern):
        return self.seconds

    def work(self, kernel):
        return spec.module("work", kernel).work(counts(), self.cell.config)

    def peaks(self):
        return {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}


def test_roofline_share():
    from bench.roofline import share
    # bytes bound: 108 B at 100 B/s = 1.08 s of a 10 s kernel
    assert share(FakeRun(10.0), "k", "gas_gather_edge_major") == \
        pytest.approx(10.8)
    assert share(FakeRun(None), "k", "gas_gather_edge_major") is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("no such chip")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
