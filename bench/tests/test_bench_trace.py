"""The trace reduction on a trace recorded on one TPU v5e chip: five
whole-tree requests of ``posdb-tree.full`` under ``--trace 1``."""
from pathlib import Path

import bench_tiny  # noqa: F401  (import paths)
import pytest

from bench import trace_reduce

TRACE = Path(__file__).parent / "data" / "full.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.load(str(TRACE)))


def test_busy_and_idle_of_the_recorded_window(reduced):
    # the run printed busy_s=4.906853457 and window_s=5.286580619
    assert reduced["busy_s"] == pytest.approx(4.906853457, rel=1e-9)
    assert reduced["window_s"] == pytest.approx(5.286580619, rel=1e-9)
    assert reduced["idle_pct"] == pytest.approx(
        100 * (1 - 4.906853457 / 5.286580619))
    assert 0 < reduced["idle_pct"] < 100


def test_top_ops_and_gaps(reduced):
    ops = reduced["device_ops"]
    assert len(ops) == 10
    assert ops[0][0] == "%while.4 while"
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    gaps = reduced["idle_gaps"]
    assert gaps[0][0] == "np.asarray(jax.Array)"
    assert sum(g for _, g in gaps) <= reduced["window_s"] - reduced["busy_s"]


def test_one_device_plane_with_ops():
    planes = trace_reduce.load(str(TRACE))
    assert len(trace_reduce.device_ops(planes)) == 1
    assert any(n == trace_reduce.WINDOW
               for n, _, _ in trace_reduce.host_events(planes))
