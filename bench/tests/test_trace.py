"""The trace reduction on a hand-made trace whose answer is worked out by
hand, and on a trace recorded on the chip."""
import glob
import os

import pytest
from jax.profiler import ProfileData

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def synthetic():
    with open(os.path.join(DATA, "synthetic.pbtxt")) as f:
        text = f.read()
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_busy_idle_and_gaps_by_hand():
    r = tr.reduce_profile(synthetic())
    # busy: [1000,4000] + [5000,5500] + [8000,10000] + [10500,11000] (the
    # first and last operations clipped to the window) = 6000 ns of 10000
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(6e-6)
    # idle gaps, longest first, named by the innermost harness span
    assert [g[0] for g in r["gaps"]] == ["bench.restart", "bench.engine_run",
                                         "bench.engine_run"]
    assert [g[1] for g in r["gaps"]] == pytest.approx([2.5e-6, 1e-6, 5e-7])


def test_operation_time_by_name():
    r = tr.reduce_profile(synthetic())
    assert tr.seconds_matching(r, "gas_gather_combine_pallas") == \
        pytest.approx(2e-6)                    # 1500 ns clipped + 500 ns
    assert tr.seconds_matching(r, "gas_scatter_reschedule_pallas") == \
        pytest.approx(2e-6)
    assert tr.seconds_matching(r, "no_such_kernel") is None
    top = tr.top_ops(r)
    assert [n for n, _ in top][:2] == ["fusion.7", "gas_gather_combine_pallas.3"] \
        or [n for n, _ in top][:2] == ["gas_gather_combine_pallas.3", "fusion.7"]
    assert dict(top)["fusion.9"] == pytest.approx(5e-7)  # clipped at the end


def test_interval_helpers():
    assert tr.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.idle_intervals([(1, 2), (4, 5)], 0, 6) == \
        [(0, 1), (2, 4), (5, 6)]


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    """A short window of the PageRank cell at a small size, traced on a
    TPU v5e: the reduction finds the device, the window, both GAS kernels,
    and a busy time inside the window."""
    r = tr.reduce(path)
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert tr.seconds_matching(r, "gas_gather_combine_pallas") > 0
    assert tr.seconds_matching(r, "gas_scatter_reschedule_pallas") > 0
    # operation time leaves out the loops that hold the operations
    assert 0 < sum(r["ops"].values()) <= r["busy_s"] * 1.001
