"""The tracer's time put on the device trace's clock (clock.py), checked on
a capture recorded on one v5e chip (data/: a traced rs83-write run that
benchmark/tests/record_capture.py kept): the tracer's EncodeService launch
spans, the profiler's trace and the anchor pair. Each kernel event on the
device has to fall inside the device leg of a launch as the tracer
recorded it and clock.py maps it."""

import gzip
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import clock, harness, spans, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KERNEL = "gf_matmul_packed"
#: the most a kernel event may lie outside every mapped leg
SLACK_S = 0.5e-3


@pytest.fixture(scope="module")
def capture():
    base = os.path.join(DATA, "rs83_write_capture")
    with gzip.open(base + ".spans.jsonl.gz", "rt", encoding="utf-8") as f:
        span_list = [spans.from_jaeger(json.loads(line)) for line in f]
    with open(base + ".anchors.json", encoding="utf-8") as f:
        anchors = json.load(f)
    trace = xplane.extract(base + ".xplane.pb")
    return SimpleNamespace(spans=span_list, trace=trace,
                           profiled=tuple(anchors["profiled"]),
                           window=anchors["window"])


def kernel_events(trace):
    return [(st, st + d) for ops in trace["devices"].values()
            for name, st, d in ops if KERNEL in name]


def outside(ev, legs) -> float:
    """Seconds by which an event reaches past the nearest leg that holds
    it best: 0 inside a leg."""
    s, e = ev
    return min(max(0.0, a - s) + max(0.0, e - b) for a, b in legs)


def test_anchor_pair_is_the_traced_window(capture):
    assert capture.trace["window"] == pytest.approx(capture.window)
    t0, t1 = capture.profiled
    w0, w1 = capture.window
    # both clocks count seconds over the same traced part of the window
    assert t1 - t0 == pytest.approx(w1 - w0, abs=2e-3)


def test_kernel_events_fall_inside_mapped_device_legs(capture):
    legs = [(a, b) for _s, a, b in clock.device_legs(capture)]
    events = kernel_events(capture.trace)
    assert len(events) >= 20 and legs
    dist = [outside(ev, legs) for ev in events]
    inside = sum(1 for d in dist if d == 0.0)
    assert inside >= 0.95 * len(events), (inside, len(events))
    assert max(dist) <= SLACK_S


def test_unmapped_tracer_time_misses_every_event(capture):
    """Without the anchor pair's offset the legs lie some 1.8e9 s away:
    the mapping, not luck, puts the kernel inside them."""
    raw = [(float(s["tags"]["device_start"]),
            float(s["tags"]["device_start"]) + float(s["tags"]["device_s"]))
           for s, _a, _b in clock.device_legs(capture)]
    assert all(outside(ev, raw) > 1.0 for ev in kernel_events(capture.trace))


def test_device_share_of_the_recorded_legs(capture):
    share = harness.load_module("metrics", "ec_launch_device_share").read(
        capture)
    assert 0.0 < share <= 100.0
    pad = harness.load_module("metrics", "ec_pad_share").read(capture)
    assert 0.0 <= pad < 100.0
