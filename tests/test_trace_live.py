"""Cross-daemon tracing with the Dapper-style span tracer
(common/tracer): a sampled client write produces ONE trace whose spans
cover client -> messenger -> osd op-queue -> sub-op forks to the shard
holders -> journal/blockstore -> the reply legs back, with parent links
forming a single tree, drained via the `dump_tracing` admin command and
rendered (critical path included) by tools/trace_tool.py.
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.rados.client import Rados
from tests.test_cluster_live import (
    EC_POOL,
    REP_POOL,
    Cluster,
    wait_until,
)


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 180))


async def dispatch_quiesce(idle=0.05, timeout=5.0):
    """Wait until NO message dispatches for `idle` seconds — the
    event-driven way to let in-flight best-effort traffic (trace
    reports) land, or to prove none is coming, without a blind sleep."""
    from ceph_tpu.msg.messenger import next_dispatch_event

    loop = asyncio.get_event_loop()
    end = loop.time() + timeout
    while loop.time() < end:
        fut = next_dispatch_event()
        try:
            await asyncio.wait_for(fut, idle)
        except asyncio.TimeoutError:
            return


def traced_cluster_cfg(**overrides):
    from tests.test_cluster_live import live_config

    cfg = live_config()
    cfg.set("tracer_enabled", True)
    cfg.set("tracer_sample_rate", 1.0)
    cfg.set("osd_objectstore", "blockstore")
    for k, v in overrides.items():
        cfg.set(k, v)
    return cfg


def assert_single_tree(spans):
    """Parent links form ONE tree: exactly one root, every non-root
    parent resolves inside the trace (no cycles by construction)."""
    ids = {s["span_id"] for s in spans}
    roots = [s for s in spans if s["parent_id"] not in ids]
    assert len(roots) == 1, [
        (s["service"], s["name"], s["parent_id"]) for s in roots
    ]
    assert roots[0]["parent_id"] is None
    return roots[0]


def test_traced_write_spans_client_to_blockstore():
    """One sampled replicated write against blockstore-backed OSDs:
    `dump_tracing` at the primary returns the COMPLETE tree — the
    client's op_submit root (reported collector-style), messenger
    send/dispatch, the op-queue wait, the op execution, and the
    journal/blockstore commit — as one trace."""

    async def main():
        cluster = Cluster(cfg=traced_cluster_cfg())
        await cluster.start()
        rados = Rados("client.sp", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        io = rados.io_ctx(REP_POOL)

        await io.write_full("traced-obj", b"t" * 9000)
        roots = [
            s for s in list(rados.objecter.tracer._ring)
            if s["name"] == "op_submit"
            and s["tags"].get("object") == "traced-obj"
        ]
        assert roots, "client root span missing"
        trace_id = roots[-1]["trace_id"]
        primary = rados.objecter._calc_target(REP_POOL, "traced-obj")
        # the client ships its spans collector-style (trace_report over
        # the messenger): wait for the root to land at the primary
        posd = cluster.osds[primary]
        await wait_until(
            lambda: any(
                s["trace_id"] == trace_id and s["name"] == "op_submit"
                for s in list(posd.tracer._ring)
            )
        )
        dump = await rados.objecter.osd_admin(primary, "dump_tracing")
        assert dump["num_traces"] >= 1
        trace = next(
            t for t in dump["traces"] if t["trace_id"] == trace_id
        )
        spans = trace["spans"]
        names = {s["name"] for s in spans}
        services = {s["service"] for s in spans}
        # every layer of the acceptance criterion is present
        assert "op_submit" in names          # client
        assert "msg_dispatch" in names       # messenger
        assert "op_queue" in names           # osd op-queue wait
        assert "osd_op" in names             # osd execution
        assert "blockstore_txn" in names     # blockstore commit
        assert "journal_commit" in names     # KV WAL commit
        assert "client.sp" in services
        assert f"osd.{primary}" in services
        root = assert_single_tree(spans)
        assert root["name"] == "op_submit"
        # replica fan-out forked child sub-op spans
        assert any(
            s["name"] == "subop_rep_ops" for s in spans
        ), names
        # timings are sane: children start at/after the root
        t0 = root["start"]
        assert all(s["start"] >= t0 - 0.001 for s in spans)

        # an UNSAMPLED op leaves nothing behind
        cluster.cfg.set("tracer_sample_rate", 0.0)
        await io.write_full("untraced", b"u" * 2000)
        await dispatch_quiesce()  # any report in flight would dispatch
        dump2 = await rados.objecter.osd_admin(primary, "dump_tracing")
        assert not any(
            s["tags"].get("object", "").endswith("untraced")
            for t in dump2["traces"] for s in t["spans"]
        )
        await rados.shutdown()
        await cluster.stop()

    run(main())


@pytest.mark.slow
def test_vstart_traced_slow_write_renders_critical_path(tmp_path):
    """The thorough variant: an EC write traced end to end with JSONL
    export; the op is forced over slow_op_seconds so the slow-request
    warning fires (with its trace id) the moment the periodic check
    sees it; trace_tool renders the tree + critical path from the
    export file."""

    async def main():
        export = tmp_path / "trace.jsonl"
        cfg = traced_cluster_cfg(
            tracer_export_path=str(export),
            slow_op_seconds=0.0,  # every in-flight op is "slow"
        )
        cluster = Cluster(cfg=cfg)
        await cluster.start()
        rados = Rados("client.tv", cluster.monmap, config=cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        io = rados.io_ctx(EC_POOL)
        data = np.random.default_rng(7).integers(
            0, 256, 30_000, np.uint8
        ).tobytes()
        await io.write_full("slow-obj", data)
        assert await io.read("slow-obj") == data

        roots = [
            s for s in list(rados.objecter.tracer._ring)
            if s["name"] == "op_submit"
            and s["tags"].get("object") == "slow-obj"
        ]
        trace_id = roots[0]["trace_id"]
        await asyncio.sleep(0.5)  # slow-op scan + trace_report + export

        # the slow-request warning line appeared in the primary's log
        # ring, tagged with the op's trace id
        primary = rados.objecter._calc_target(EC_POOL, "slow-obj")
        logd = await rados.objecter.osd_admin(primary, "log dump")
        slow_lines = [
            e["message"] for e in logd["entries"]
            if "slow request" in e["message"]
        ]
        assert slow_lines, "no slow-request warning emitted"
        assert any("trace=" in line for line in slow_lines)

        # EC fan-out: the trace covers shard sub-ops + the encode leg
        dump = await rados.objecter.osd_admin(primary, "dump_tracing")
        trace = next(
            t for t in dump["traces"] if t["trace_id"] == trace_id
        )
        names = {s["name"] for s in trace["spans"]}
        assert "subop_ec_sub_write" in names
        assert "encode_wait" in names or "encode_batch" in names
        assert_single_tree(trace["spans"])

        # historic ops carry the span timeline
        hist = await rados.objecter.osd_admin(
            primary, "dump_historic_ops"
        )
        traced_ops = [
            o for o in hist["ops"] if o.get("trace_id") == trace_id
        ]
        assert traced_ops and traced_ops[0]["span"]["duration"] > 0

        await rados.shutdown()
        await cluster.stop()

        # exported JSONL renders with a critical path starting at the
        # client root
        from tools import trace_tool

        spans = trace_tool.load_spans(str(export))
        mine = [s for s in spans if s["trace_id"] == trace_id]
        assert mine
        text = trace_tool.render_trace(mine)
        assert "critical path" in text
        assert "op_submit" in text
        cp = trace_tool.critical_path(mine)
        assert cp and cp[0]["name"] == "op_submit"

    run(main())


def ring_spans(rados, cluster, trace_id=None) -> dict:
    """Every sampled span the client and the OSDs hold, by span id (the
    client's spans also reach its primary by trace_report)."""
    out = {}
    for tr in [rados.objecter.tracer] + [
        o.tracer for o in cluster.osds.values()
    ]:
        for s in list(tr._ring):
            if trace_id is None or s["trace_id"] == trace_id:
                out[s["span_id"]] = s
    return out


def test_traced_writes_show_every_daemon_and_reply_leg():
    """One sampled write to an EC pool and one to a replicated pool, each
    a single trace: spans from the client and at least three OSDs, the
    shard applies on OSDs other than the primary under the primary's fork
    spans, and the messenger's send and dispatch on the reply legs, both
    the sub-op acks and the op's reply. An unsampled op leaves no span."""

    async def main():
        cluster = Cluster(cfg=traced_cluster_cfg())
        await cluster.start()
        rados = Rados("client.tr", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        data = np.random.default_rng(5).integers(
            0, 256, 20_000, np.uint8
        ).tobytes()
        for pool, obj, fork in ((EC_POOL, "traced-ec", "subop_ec_sub_write"),
                                (REP_POOL, "traced-rep", "subop_rep_ops")):
            await rados.io_ctx(pool).write_full(obj, data)
            root = next(
                s for s in list(rados.objecter.tracer._ring)
                if s["name"] == "op_submit"
                and s["tags"].get("object") == obj
            )
            await dispatch_quiesce()  # replies' send spans close after
            spans = list(ring_spans(
                rados, cluster, root["trace_id"]).values())
            assert assert_single_tree(spans)["span_id"] == root["span_id"]
            primary = f"osd.{rados.objecter._calc_target(pool, obj)}"
            services = {s["service"] for s in spans}
            assert "client.tr" in services
            assert len({v for v in services if v.startswith("osd.")}) >= 3
            by_id = {s["span_id"]: s for s in spans}
            forks = {s["span_id"] for s in spans
                     if s["name"] == fork and s["service"] == primary}
            assert len(forks) >= 2
            # the shard applies run on the peers, under the fork spans
            applied = {s["service"] for s in spans
                       if s["parent_id"] in forks
                       and s["name"] == "op_queue"}
            assert applied and primary not in applied
            # both reply legs are traced, on both sides
            legs = {(s["name"], s["tags"].get("type"), s["service"])
                    for s in spans}
            assert ("msg_send", "osd_op_reply", primary) in legs
            assert ("msg_dispatch", "osd_op_reply", "client.tr") in legs
            acks = [s for s in spans if s["name"] in ("msg_send",
                                                      "msg_dispatch")
                    and s["tags"].get("type") == "sub_reply"]
            assert {s["service"] for s in acks
                    if s["name"] == "msg_dispatch"} == {primary}
            assert {s["service"] for s in acks
                    if s["name"] == "msg_send"} == applied
            assert all(by_id[s["parent_id"]]["name"] == fork for s in acks)

        # an UNSAMPLED op leaves nothing behind, on any daemon
        before = set(ring_spans(rados, cluster))
        cluster.cfg.set("tracer_sample_rate", 0.0)
        await rados.io_ctx(EC_POOL).write_full("untraced", data)
        await dispatch_quiesce()
        assert set(ring_spans(rados, cluster)) == before
        await rados.shutdown()
        await cluster.stop()

    run(main())


def test_coalesced_subop_acks_wait_under_the_fork_span():
    """Concurrent EC writes coalesce sub-ops to the same peer into one
    subop_batch frame; each inner op's hold, from its own ack to the
    send of the reply that carries it, is a subop_ack_wait span under the
    sender's fork span, and the coalesced frames' messenger legs land on
    every inner op's trace."""

    async def main():
        cluster = Cluster(cfg=traced_cluster_cfg())
        await cluster.start()
        rados = Rados("client.co", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        io = rados.io_ctx(EC_POOL)
        payloads = np.random.default_rng(9).integers(
            0, 256, (24, 6000), np.uint8
        )
        await asyncio.gather(*(
            io.write_full(f"co{i}", payloads[i].tobytes())
            for i in range(24)
        ))
        await dispatch_quiesce()
        assert sum(o.perf.dump()["subop_batch_tx"]
                   for o in cluster.osds.values()) >= 1
        spans = ring_spans(rados, cluster)
        waits = [s for s in spans.values() if s["name"] == "subop_ack_wait"]
        assert waits
        for w in waits:
            fork = spans[w["parent_id"]]
            assert fork["name"] == "subop_ec_sub_write"
            assert fork["trace_id"] == w["trace_id"]
            assert fork["tags"]["to"] == w["service"]
            assert w["duration"] >= 0 and w["tags"]["batch"] >= 2
            # the hold ends before the fork span sees the ack
            assert w["start"] + w["duration"] <= (
                fork["start"] + fork["duration"])
        # a coalesced frame's legs: one span per inner op, on its trace
        batched = [s for s in spans.values() if s["name"] == "msg_dispatch"
                   and s["tags"].get("type") == "subop_batch"]
        assert len({s["trace_id"] for s in batched}) >= 2
        assert all(spans[s["parent_id"]]["name"] == "subop_ec_sub_write"
                   for s in batched)
        await rados.shutdown()
        await cluster.stop()

    run(main())
