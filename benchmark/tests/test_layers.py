"""The per-layer readers of the loop-lag probe, the EncodeService's launch
phases and bytes and the shard's ack holds, on spans built and worked by
hand. Tag values are strings, as the tracer's Jaeger export writes them."""

from types import SimpleNamespace

import pytest

from benchmark import clock, harness


def _span(sid, parent, name, service, start, end, trace="t", **tags):
    return {"trace_id": trace, "span_id": sid, "parent_id": parent,
            "name": name, "service": service, "start": start,
            "duration": end - start,
            "tags": {k: str(v) for k, v in tags.items()}}


def read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


#: the profiled seconds are [1000, 1010] on the tracer's clock and the
#: traced window [0.5, 10.5] on the profiler's: tracer time t lies at
#: t - 999.5 on the device trace
PROFILED = (1000.0, 1010.0)
WINDOW = [0.5, 10.5]


def _batch(sid, name, start, dev_start, dev_s, end, obj, launched):
    return _span(sid, None, name, "osd.1", start, end, path="pallas",
                 width=launched // 32, compile=False, batch=1,
                 object_bytes=obj, launch_bytes=launched,
                 pack_s=dev_start - start, device_start=dev_start,
                 device_s=dev_s, unpack_s=end - dev_start - dev_s)


LAUNCHES = [
    # device legs [1001.0, 1001.4] and [1003.0, 1003.2]: on the device
    # trace [1.5, 1.9] and [3.5, 3.7]
    _batch("e", "encode_batch", 1000.9, 1001.0, 0.4, 1001.5, 3000, 4096),
    _batch("d", "decode_batch", 1002.8, 1003.0, 0.2, 1003.3, 1024, 1024),
]


def _ctx(spans, devices=None, traces=()):
    return SimpleNamespace(
        spans=spans, traces=list(traces), profiled=PROFILED,
        trace={"window": WINDOW, "devices": devices or {}, "host": []})


def test_clock_maps_tracer_time_by_the_anchor_pair():
    ctx = _ctx(LAUNCHES)
    assert clock.offset(ctx) == pytest.approx(-999.5)
    legs = [(s["span_id"], a, b) for s, a, b in clock.device_legs(ctx)]
    assert legs == [("e", pytest.approx(1.5), pytest.approx(1.9)),
                    ("d", pytest.approx(3.5), pytest.approx(3.7))]
    # a leg that the traced window does not hold whole is left out
    early = _batch("x", "encode_batch", 999.8, 999.9, 0.2, 1000.2, 1, 1)
    assert len(clock.device_legs(_ctx(LAUNCHES + [early]))) == 2


def test_device_share_of_the_legs():
    devices = {
        # chip 0: 0.1 s inside the first leg (two overlapping ops count
        # once), 0.05 s in the second, and an op outside both
        "/device:TPU:0": [["k", 1.6, 0.1], ["k", 1.65, 0.05],
                          ["k", 3.6, 0.05], ["k", 5.0, 1.0]],
        # chip 1: 0.1 s across the second leg's end (half of it inside)
        "/device:TPU:1": [["k", 3.65, 0.1]],
    }
    got = read("ec_launch_device_share", _ctx(LAUNCHES, devices))
    # busy (0.15 + 0.05) / 2 chips over legs of 0.4 + 0.2 s
    assert got == pytest.approx(100.0 * 0.1 / 0.6)
    assert read("ec_launch_device_share", _ctx(LAUNCHES)) is None
    bare = [dict(s, tags={}) for s in LAUNCHES]
    assert read("ec_launch_device_share", _ctx(bare, devices)) is None


def test_pad_share_from_object_and_launch_bytes():
    # 1 - (3000 + 1024) / (4096 + 1024)
    assert read("ec_pad_share", _ctx(LAUNCHES)) == pytest.approx(
        100.0 * (1 - 4024 / 5120))
    assert read("ec_pad_share", _ctx([dict(s, tags={"path": "pallas"})
                                      for s in LAUNCHES])) is None


def test_pad_share_of_a_known_mix():
    """Objects of 3, 5 and 8 KiB alone in buckets of 4, 8 and 8 KiB: 4 of
    the 20 KiB launched are padding."""
    kib = 1024
    spans_ = [_batch(str(i), "encode_batch", 1001.0 + i, 1001.1 + i, 0.01,
                     1001.2 + i, obj * kib, bucket * kib)
              for i, (obj, bucket) in enumerate([(3, 4), (5, 8), (8, 8)])]
    assert read("ec_pad_share", _ctx(spans_)) == pytest.approx(20.0)


def test_launch_loop_ms_is_the_mean_batch_span():
    # (0.6 + 0.5) / 2 s
    assert read("ec_launch_loop_ms", _ctx(LAUNCHES)) == pytest.approx(550.0)
    assert read("ec_launch_loop_ms", _ctx([])) is None


def test_loop_lag_p95():
    lags = [_span(f"l{i}", None, "loop_lag", "mon.a", 1000.0 + i * 0.01,
                  1000.0 + i * 0.01 + ms / 1e3, trace=f"t{i}")
            for i, ms in enumerate(range(1, 21))]
    # quantiles(n=20) of 1..20 ms, exclusive method: 19.95 ms
    assert read("loop_lag_p95_ms", _ctx(lags)) == pytest.approx(19.95)
    assert read("loop_lag_p95_ms", _ctx(lags[:1])) is None


#: a coalesced write: the shard's ack waits 2 s for its batch-mates, then
#: the reply's legs take 0.5 s; a second op's shard acked in a frame alone
OP = [
    _span("r", None, "op_submit", "client.bench", 0.0, 10.0),
    _span("c", "r", "osd_op", "osd.1", 1.0, 9.0),
    _span("f", "c", "subop_ec_sub_write", "osd.1", 2.0, 8.0),
    _span("q", "f", "op_queue", "osd.2", 3.0, 4.0),
    _span("w", "f", "subop_ack_wait", "osd.2", 4.0, 6.0),
    _span("s", "f", "msg_send", "osd.2", 6.0, 6.5, type="sub_reply"),
    _span("p", "f", "msg_dispatch", "osd.1", 7.0, 7.5, type="sub_reply"),
]
SOLO = [dict(s, trace_id="u") for s in OP if s["span_id"] != "w"]


def test_subop_ack_wait_self_time():
    lag = [_span("l", None, "loop_lag", "mon.a", 1000.0, 1000.001)]
    # 2 s held in one op of two
    assert read("subop_ack_wait_ms", _ctx(lag, traces=[OP, SOLO])) == (
        pytest.approx(1000.0))
    # instrumented, and no op of the window held an ack: 0
    assert read("subop_ack_wait_ms", _ctx(lag, traces=[SOLO])) == 0.0
    # a program without the span records no loop_lag either: nothing
    assert read("subop_ack_wait_ms", _ctx([], traces=[SOLO])) is None
    assert read("subop_ack_wait_ms", _ctx([], traces=[OP])) == (
        pytest.approx(2000.0))


def test_decode_wait_self_time():
    read_op = [
        _span("r", None, "op_submit", "client.bench", 0.0, 4.0),
        _span("c", "r", "osd_op", "osd.1", 0.5, 3.5),
        _span("w", "c", "decode_wait", "osd.1", 1.0, 3.0),
        # the launch ran inside the wait: off the path
        _span("b", "c", "decode_batch", "osd.1", 1.5, 2.0),
    ]
    assert read("ec_decode_wait_ms", _ctx([], traces=[read_op])) == (
        pytest.approx(2000.0))
    assert read("ec_decode_wait_ms", _ctx([], traces=[OP])) is None
