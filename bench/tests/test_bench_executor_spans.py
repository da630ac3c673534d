"""The executor's span readers (``launch_ms``, ``device_wait_ms``): the
mean per request of their spans, and nothing where a run has no spans
(an untraced run, or a program that records none)."""
import types

import pytest
from bench_tiny import REPO

from bench import harness

READERS = ("launch_ms", "device_wait_ms")


def reader(name):
    return harness.load_module(REPO / "bench" / "metrics" / f"{name}.py").read


def _span(i, name, dur, parent):
    return {"type": "span", "id": i, "parent": parent, "name": name,
            "ts_us": 0.0, "dur_us": dur, "attrs": {}}


def _run(spans):
    return types.SimpleNamespace(served=[], window_s=1.0, setup_s=1.0,
                                 spans=spans, device=None,
                                 answer_counts=None, peaks=None)


def test_mean_per_request_of_the_executor_spans():
    # two requests; the second has two buckets
    spans = [_span(1, "launch", 200.0, 0), _span(3, "device_wait", 900.0, 2),
             _span(2, "dispatch", 1000.0, 0), _span(0, "request", 1300, None),
             _span(5, "launch", 100.0, 4), _span(6, "launch", 300.0, 4),
             _span(8, "device_wait", 500.0, 7),
             _span(7, "dispatch", 600.0, 4), _span(4, "request", 1200, None)]
    run = _run(spans)
    assert reader("launch_ms")(run) == pytest.approx((200 + 400) / 2 / 1e3)
    assert reader("device_wait_ms")(run) == pytest.approx(
        (900 + 500) / 2 / 1e3)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read(name):
    assert reader(name)(_run(None)) is None            # untraced
    # a traced program that records requests but not these spans
    assert reader(name)(_run([_span(0, "request", 10.0, None)])) is None
