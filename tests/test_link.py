"""Data-plane flight recorder + slow-link sentinel (docs/architecture.md
"Data-plane observability").

Covers the cross-engine hop-telemetry contract (py vs native produce the
SAME hop-record schema and consistent stall/byte accounting on every
topology x codec combination), the monotonic cross-reconfigure counter
bank, the Manager's per-neighbor link-health observation, the native
lighthouse's slow-link sentinel arc (hysteresis, edge naming, auto-drain
floor), the obs rollups (link_attribution, Perfetto hop track), the
unified worker /metrics endpoint, and the static registry greps pinning
the new span/gauge names — the test_flight.py convention."""

from __future__ import annotations

import json
import os
import re
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional
from unittest.mock import MagicMock

import numpy as np
import pytest

from test_manager import make_manager, make_quorum, store  # noqa: F401
from torchft_tpu._native import StoreServer
from torchft_tpu.collectives import (
    HOP_RECORD_FIELDS,
    HopRecorder,
    TCPCollective,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PREFIX_COUNTER = [0]
_PREFIX_LOCK = threading.Lock()


def fresh_prefix() -> str:
    with _PREFIX_LOCK:
        _PREFIX_COUNTER[0] += 1
        return f"link/{_PREFIX_COUNTER[0]}"


def _read(relpath: str) -> str:
    with open(os.path.join(REPO, relpath), "r", encoding="utf-8") as f:
        return f.read()


def run_ranks(store, world_size, fn, **collective_kw):  # noqa: F811
    prefix = fresh_prefix()
    collectives = [
        TCPCollective(timeout=15.0, **collective_kw) for _ in range(world_size)
    ]

    def worker(rank: int):
        c = collectives[rank]
        c.configure(f"{store.address()}/{prefix}", rank, world_size)
        try:
            return fn(c, rank)
        finally:
            c.shutdown()

    with ThreadPoolExecutor(max_workers=world_size) as pool:
        futs = [pool.submit(worker, r) for r in range(world_size)]
        return [f.result(timeout=60) for f in futs]


ENGINES = ["py", "native"]


# ---------------------------------------------------------------------------
# Engine telemetry parity: schema + accounting across topology x codec
# ---------------------------------------------------------------------------


def _one_allreduce(c, rank, codec: Optional[str]):
    x = np.full(40000, float(rank + 1), dtype=np.float32)
    kw = {"wire_codec": codec} if codec else {}
    out = c.allreduce([x], op="sum", **kw).wait(timeout=30)[0]
    assert out.shape == x.shape
    return {
        "stats": c.lane_stats(),
        "records": c.hop_records(),
        "engine": c.ring_engine,
    }


@pytest.mark.parametrize("codec", [None, "int8"])
@pytest.mark.parametrize("wire_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("lanes,world,topology", [
    (1, 2, None),
    (2, 2, None),
    (2, 4, "ring2d"),
])
def test_hop_telemetry_parity_py_vs_native(
    store, lanes, world, topology, wire_dtype, codec  # noqa: F811
) -> None:
    """Both engines produce hop records with EXACTLY the pinned schema and
    the same per-tier hop counts for the same topology/codec config, with
    stall/byte accounting internally consistent (every hop's payload is
    accounted, every timing field non-negative)."""
    if codec == "int8" and wire_dtype == "bf16":
        pytest.skip("codec supersedes wire dtype; one lossy axis at a time")
    per_engine = {}
    for engine in ENGINES:
        results = run_ranks(
            store, world, lambda c, r: _one_allreduce(c, r, codec),
            lanes=lanes, wire_dtype=wire_dtype, topology=topology,
            engine=engine, chunk_bytes=16 << 10,
        )
        r0 = results[0]
        if engine == "native":
            assert r0["engine"] == "native"
        # Schema: every record carries exactly HOP_RECORD_FIELDS.
        assert r0["records"], "no hop records retained"
        for rec in r0["records"]:
            assert set(rec.keys()) == set(HOP_RECORD_FIELDS), rec
            assert rec["send_s"] >= 0 and rec["recv_s"] >= 0
            assert rec["comb_s"] >= 0 and rec["nbytes"] >= 0
            assert rec["ts"] > 1e9  # wall clock, both engines
            assert rec["tier"] in (0, 1, 2)
            assert 0 <= rec["lane"] < lanes
        hops = r0["stats"]["hops"]
        assert set(hops["flat"].keys()) == {
            "hops", "send_block_s", "recv_wait_s", "combine_s", "shape_s",
        }
        if topology == "ring2d":
            assert "row" in hops and "col" in hops
            assert hops["row"]["hops"] > 0
        else:
            assert hops["flat"]["hops"] > 0
        total_hops = sum(t["hops"] for t in hops.values())
        assert total_hops == len(r0["records"])  # sample=1 retains all
        # Byte consistency: recorded hop payloads never exceed the lane
        # counters (which additionally include frame headers).
        sent = sum(r0["stats"]["sent"])
        for t in (r0["stats"].get("tiers") or {}).values():
            sent += sum(t["sent"])
        assert sum(rec["nbytes"] for rec in r0["records"]) <= sent
        per_engine[engine] = {
            "hops": total_hops,
            "per_tier": {k: v["hops"] for k, v in hops.items()},
        }
    if len(per_engine) == 2:
        # The engines must agree on the hop COUNT structure exactly (same
        # stripe/tier math on both sides — the interop contract).
        assert per_engine["py"] == per_engine["native"], per_engine


def test_hop_sample_knob_disables_timeline_keeps_aggregates(
    store, monkeypatch  # noqa: F811
) -> None:
    monkeypatch.setenv("TPUFT_HOP_SAMPLE", "0")
    results = run_ranks(store, 2, lambda c, r: _one_allreduce(c, r, None))
    r0 = results[0]
    assert r0["records"] == []  # timeline off
    assert r0["stats"]["hops"]["flat"]["hops"] > 0  # aggregates stay on


def test_hop_recorder_bounded_ring() -> None:
    rec = HopRecorder(sample=1, cap=16)
    for i in range(100):
        rec.record(0, 0, 9, 0.001, 0.002, 0.0005, 64, 1000.0 + i)
    records = rec.records()
    assert len(records) == 16
    assert records[0]["ts"] == 1084.0  # oldest retained
    assert rec.stats(0)["hops"] == 100  # aggregates unbounded
    rec2 = HopRecorder(sample=4, cap=16)
    for i in range(16):
        rec2.record(0, 0, 9, 0.0, 0.0, 0.0, 1, float(i))
    assert len(rec2.records()) == 4  # every 4th sampled


# ---------------------------------------------------------------------------
# Monotonic cross-reconfigure counters (the scrape-visible bank)
# ---------------------------------------------------------------------------


def test_lane_totals_monotonic_across_reconfigure(store) -> None:  # noqa: F811
    prefix = fresh_prefix()
    collectives = [TCPCollective(timeout=15.0, lanes=2) for _ in range(2)]
    snapshots: List[List[dict]] = [[], []]

    def worker(rank: int) -> None:
        c = collectives[rank]
        for gen in range(2):
            c.configure(f"{store.address()}/{prefix}_{gen}", rank, 2)
            x = np.full(4000, float(rank + 1), dtype=np.float32)
            c.allreduce([x], op="sum").wait(timeout=30)
            # Live stats RESET per configure; totals must not.
            snapshots[rank].append(
                {"stats": c.lane_stats(), "totals": c.lane_totals()}
            )
        c.shutdown()
        snapshots[rank].append({"totals": c.lane_totals()})

    with ThreadPoolExecutor(max_workers=2) as pool:
        futs = [pool.submit(worker, r) for r in range(2)]
        for f in futs:
            f.result(timeout=60)

    for rank in range(2):
        gen0, gen1, final = snapshots[rank]
        # The per-configure view DID reset (second gen starts fresh) ...
        assert gen1["stats"]["hops"]["flat"]["hops"] <= gen0["totals"]["hops"]["flat"]["hops"] + gen1["totals"]["hops"]["flat"]["hops"]
        # ... while the bank is strictly monotonic and banked the closed
        # generation at the reconfigure.
        assert gen1["totals"]["sent_bytes"] > gen0["totals"]["sent_bytes"]
        assert gen1["totals"]["hops"]["flat"]["hops"] > gen0["totals"]["hops"]["flat"]["hops"]
        assert gen1["totals"]["reconfigures"] >= 1
        # Post-shutdown the whole history is banked, nothing lost — and
        # nothing DOUBLE-counted: banking resets the recorder, so the
        # post-abort read equals the pre-abort cumulative view exactly
        # (a bank that left the live aggregates behind would read ~2x
        # here and then drop at the next configure — a backwards counter).
        assert final["totals"]["sent_bytes"] == gen1["totals"]["sent_bytes"]
        assert (final["totals"]["hops"]["flat"]["hops"]
                == gen1["totals"]["hops"]["flat"]["hops"])
        assert final["totals"]["reconfigures"] == 2


def test_set_link_shaping_mid_run(store) -> None:  # noqa: F811
    """Mid-run reshaping really slows the modeled link (both engines pace
    in whoever owns the sends) and the shaping sleep lands in the hop
    aggregates' shape_s bucket."""
    os.environ["TPUFT_SHAPED_LINK"] = "400:1"
    try:
        def body(c, rank):
            x = np.full(200_000, 1.0, dtype=np.float32)
            t0 = time.monotonic()
            c.allreduce([x], op="sum").wait(timeout=30)
            fast = time.monotonic() - t0
            c.set_link_shaping(8.0, 1.0)  # 50x slower outbound
            t0 = time.monotonic()
            c.allreduce([x], op="sum").wait(timeout=60)
            slow = time.monotonic() - t0
            return fast, slow, c.lane_stats()["hops"]["flat"]["shape_s"]

        results = run_ranks(store, 2, body, lanes=1, wire_dtype="f32")
        for fast, slow, shape_s in results:
            assert slow > fast * 3, (fast, slow)
            assert shape_s > 0.0
    finally:
        del os.environ["TPUFT_SHAPED_LINK"]


def test_set_link_shaping_on_unshaped_collective(store) -> None:  # noqa: F811
    """A collective configured WITHOUT TPUFT_SHAPED_LINK can still be
    re-shaped mid-run, and the shaping sleep is attributed to shape_s in
    whichever engine owns the pacing (the native-counter hooks are wired
    lazily — a fresh Python shaper reading its own zeros while the native
    pacer sleeps would silently zero the shaping bucket)."""
    assert "TPUFT_SHAPED_LINK" not in os.environ

    def body(c, rank):
        x = np.full(100_000, 1.0, dtype=np.float32)
        c.allreduce([x], op="sum").wait(timeout=30)
        assert c.lane_stats()["hops"]["flat"]["shape_s"] == 0.0
        c.set_link_shaping(16.0, 1.0)
        c.allreduce([x], op="sum").wait(timeout=60)
        return c.lane_stats()["hops"]["flat"]["shape_s"], c.ring_engine

    for shape_s, engine in run_ranks(store, 2, body, lanes=1, wire_dtype="f32"):
        assert shape_s > 0.0, engine


# ---------------------------------------------------------------------------
# Manager: link-health observation + heartbeat push
# ---------------------------------------------------------------------------


from test_manager import FakeCollective  # noqa: E402


class _LaneStatsCollective(FakeCollective):
    """FakeCollective whose lane_stats advances per call — enough
    hop-delta signal for the Manager's link observation."""

    def __init__(self) -> None:
        super().__init__()
        self.calls = 0

    def lane_stats(self) -> dict:
        self.calls += 1
        n = self.calls
        return {
            "lanes": 2,
            "topology": "ring",
            "engine": "py",
            "sent": [n * 1_000_000],
            "recv": [n * 1_000_000],
            "hops": {
                "flat": {
                    "hops": n * 4,
                    "send_block_s": n * 0.01,
                    "recv_wait_s": n * 0.05,
                    "combine_s": n * 0.001,
                    "shape_s": 0.0,
                }
            },
        }


def test_manager_observes_link_health_and_pushes_status(
    store, tmp_path, monkeypatch  # noqa: F811
) -> None:
    """Two traffic-bearing commits: the second produces a link-health
    observation (delta window), lands the EWMA fields in step_summary, and
    rides the post-commit SetStatus push (heartbeat fields 11-13)."""
    metrics_path = tmp_path / "m.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(metrics_path))
    client = MagicMock()
    client._quorum.return_value = make_quorum(max_world_size=2)
    client.should_commit.return_value = True
    manager, collective, _ = make_manager(
        store, collective=_LaneStatsCollective(), client_mock=client
    )
    try:
        for _ in range(2):
            manager.start_quorum()
            manager.allreduce(np.full(64, 1.0, dtype=np.float32)).result()
            assert manager.should_commit()
        events = [json.loads(l) for l in metrics_path.read_text().splitlines()]
        summaries = [e for e in events if e["event"] == "step_summary"]
        assert len(summaries) == 2
        assert "link_send_gbps" not in summaries[0]  # first window: no delta
        second = summaries[1]
        # delta: 1 MB over 0.01 s send-blocked = 0.1 GB/s; 0.05 s recv-wait
        # = 0.02 GB/s; 4 hops over 0.05 s = 12.5 ms/hop.
        assert second["link_send_gbps"] == pytest.approx(0.1, rel=0.01)
        assert second["link_recv_gbps"] == pytest.approx(0.02, rel=0.01)
        assert second["link_hop_rtt_ms"] == pytest.approx(12.5, rel=0.01)
        srv = manager._manager_server
        push = srv.set_status.call_args_list[-1].args
        # (step, state, ewma, last, gbps, ec*3, link_recv, link_send, rtt)
        assert push[8] == pytest.approx(0.02, rel=0.01)
        assert push[9] == pytest.approx(0.1, rel=0.01)
        assert push[10] == pytest.approx(12.5, rel=0.01)
    finally:
        manager.shutdown()


def test_manager_hop_dump_on_shutdown(
    store, tmp_path, monkeypatch  # noqa: F811
) -> None:
    monkeypatch.setenv("TPUFT_HOP_DUMP_DIR", str(tmp_path))
    client = MagicMock()
    client._quorum.return_value = make_quorum(max_world_size=2)
    client.should_commit.return_value = True

    class _HopCollective(_LaneStatsCollective):
        def hop_records(self):
            return [
                {"ts": 1000.0 + i, "tier": 0, "lane": 0, "tag": 9,
                 "send_s": 0.001, "recv_s": 0.01, "comb_s": 0.0,
                 "nbytes": 64}
                for i in range(3)
            ]

    manager, _, _ = make_manager(
        store, collective=_HopCollective(), client_mock=client
    )
    manager.shutdown()
    dumps = [p for p in os.listdir(tmp_path) if p.startswith("hops_")]
    assert len(dumps) == 1
    from torchft_tpu.obs.trace import hops_to_stream, load_hops_dump

    doc = load_hops_dump(os.path.join(tmp_path, dumps[0]))
    stream = hops_to_stream(doc)
    assert len(stream) == 3
    assert all(ev["event"] == "hop" for ev in stream)
    assert stream[0]["replica_id"] == doc["replica_id"]


# ---------------------------------------------------------------------------
# Slow-link sentinel arc (native lighthouse)
# ---------------------------------------------------------------------------


def _scrape(lighthouse) -> dict:
    port = lighthouse.http_address().rsplit(":", 1)[1]
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ).read().decode()
    metrics = {}
    for line in body.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name_labels, _, value = line.rpartition(" ")
        metrics[name_labels] = float(value)
    return metrics


def _get_json(lighthouse, path: str) -> dict:
    port = lighthouse.http_address().rsplit(":", 1)[1]
    return json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ).read().decode()
    )


def test_link_sentinel_arc_detects_and_recovers(monkeypatch) -> None:
    """healthy -> suspect -> degraded on a collapsed outbound goodput,
    slow_link alert on /alerts.json (naming the reporter in
    src_replica_id), hysteresis both directions, alert resolves on
    recovery — the straggler arc's data-plane twin."""
    monkeypatch.setenv("TPUFT_LINK_RATIO", "3.0")
    monkeypatch.setenv("TPUFT_LINK_WARMUP_STEPS", "0")
    monkeypatch.setenv("TPUFT_LINK_GRACE_STEPS", "2")
    monkeypatch.setenv("TPUFT_LINK_AUTO_DRAIN", "0")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20,
    )
    try:
        client = LighthouseClient(server.address())

        def hb(rid: str, step: int, send_gbps: float, recv_gbps=0.5,
               rtt_ms=5.0) -> None:
            client.heartbeat(
                rid, step=step, state="step",
                link_recv_gbps=recv_gbps, link_send_gbps=send_gbps,
                link_hop_rtt_ms=rtt_ms,
            )

        hb("0:fast", 1, 1.0)
        hb("1:slow", 1, 1.0)
        m = _scrape(server)
        assert m['tpuft_link_state{replica="1:slow"}'] == 0
        assert m["tpuft_links_degraded"] == 0
        assert m['tpuft_link_send_gbps{replica="1:slow"}'] == 1.0
        assert m['tpuft_link_hop_rtt_ms{replica="1:slow"}'] == 5.0

        # Outbound goodput collapses 10x -> suspect on the first scored
        # step (upper median of [0.1, 1.0] is 1.0 -> ratio 10 >= 3).
        hb("1:slow", 2, 0.1)
        m = _scrape(server)
        assert m['tpuft_link_state{replica="1:slow"}'] == 1
        assert m['tpuft_link_slowness_ratio{replica="1:slow"}'] == pytest.approx(10.0)
        assert m["tpuft_alerts_active"] == 0

        # Grace steps -> degraded + alert.  No formed quorum here, so the
        # alert names the reporter itself (successor unknown).
        hb("0:fast", 2, 1.0)
        hb("1:slow", 3, 0.1)
        m = _scrape(server)
        assert m['tpuft_link_state{replica="1:slow"}'] == 2
        assert m['tpuft_link_state{replica="0:fast"}'] == 0
        assert m["tpuft_links_degraded"] == 1
        assert m["tpuft_alerts_active"] == 1
        alerts = _get_json(server, "/alerts.json")
        (alert,) = [a for a in alerts["alerts"] if a["active"]]
        assert alert["kind"] == "slow_link"
        assert alert["src_replica_id"] == "1:slow"
        assert alert["replica_id"] == "1:slow"  # fallback: no quorum order
        assert alert["gbps"] == pytest.approx(0.1)
        assert alert["ratio"] == pytest.approx(10.0)

        # A heartbeat without a step advance is not an observation.
        hb("1:slow", 3, 0.1)
        assert server.link_state("1:slow") == 2

        # Recovery needs the full grace of on-pace steps.
        hb("1:slow", 4, 1.0)
        assert server.link_state("1:slow") == 2
        hb("1:slow", 5, 1.0)
        m = _scrape(server)
        assert m['tpuft_link_state{replica="1:slow"}'] == 0
        assert m["tpuft_alerts_active"] == 0
        alerts = _get_json(server, "/alerts.json")
        assert all(a["resolved_ms"] > 0 for a in alerts["alerts"])
    finally:
        server.shutdown()


def test_link_sentinel_suspect_cleared_by_one_good_step(monkeypatch) -> None:
    monkeypatch.setenv("TPUFT_LINK_RATIO", "3.0")
    monkeypatch.setenv("TPUFT_LINK_WARMUP_STEPS", "0")
    monkeypatch.setenv("TPUFT_LINK_GRACE_STEPS", "2")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20,
    )
    try:
        client = LighthouseClient(server.address())
        client.heartbeat("a", step=1, state="step", link_send_gbps=1.0)
        client.heartbeat("b", step=1, state="step", link_send_gbps=1.0)
        client.heartbeat("b", step=2, state="step", link_send_gbps=0.1)
        assert server.link_state("b") == 1
        client.heartbeat("b", step=3, state="step", link_send_gbps=1.0)
        assert server.link_state("b") == 0  # a blip is not a degraded edge
        m = _scrape(server)
        assert m["tpuft_alerts_active"] == 0
    finally:
        server.shutdown()


def test_link_sentinel_warmup_gate(monkeypatch) -> None:
    monkeypatch.setenv("TPUFT_LINK_RATIO", "3.0")
    monkeypatch.setenv("TPUFT_LINK_WARMUP_STEPS", "10")
    monkeypatch.setenv("TPUFT_LINK_GRACE_STEPS", "1")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20,
    )
    try:
        client = LighthouseClient(server.address())
        client.heartbeat("a", step=1, state="step", link_send_gbps=1.0)
        for step in range(1, 6):
            client.heartbeat("b", step=step, state="step", link_send_gbps=0.05)
        # Persistently slow but inside the warmup: suspect, never degraded.
        assert server.link_state("b") == 1
        m = _scrape(server)
        assert m["tpuft_alerts_active"] == 0
    finally:
        server.shutdown()


def test_link_sentinel_auto_drain_respects_min_replicas(monkeypatch) -> None:
    """Auto-drain marks the alert's endpoint draining — but never below
    the min_replicas floor."""
    monkeypatch.setenv("TPUFT_LINK_RATIO", "3.0")
    monkeypatch.setenv("TPUFT_LINK_WARMUP_STEPS", "0")
    monkeypatch.setenv("TPUFT_LINK_GRACE_STEPS", "1")
    monkeypatch.setenv("TPUFT_LINK_AUTO_DRAIN", "1")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    for min_replicas, expect_drain in ((1, True), (3, False)):
        server = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=min_replicas,
            join_timeout_ms=200, quorum_tick_ms=20,
        )
        try:
            client = LighthouseClient(server.address())
            client.heartbeat("a", step=1, state="step", link_send_gbps=1.0)
            client.heartbeat("b", step=1, state="step", link_send_gbps=1.0)
            client.heartbeat("c", step=1, state="step", link_send_gbps=1.0)
            client.heartbeat("b", step=2, state="step", link_send_gbps=0.05)
            client.heartbeat("b", step=3, state="step", link_send_gbps=0.05)
            assert server.link_state("b") == 2
            status = _get_json(server, "/status.json")
            drained = status.get("draining") or []
            if expect_drain:
                # No formed quorum -> the endpoint falls back to the
                # reporter; the point here is the floor gate.
                assert drained == ["b"]
                alerts = _get_json(server, "/alerts.json")
                (alert,) = [a for a in alerts["alerts"] if a["active"]]
                assert alert["auto_drained"] is True
            else:
                assert drained == []
        finally:
            server.shutdown()


def test_link_health_survives_ha_replication(monkeypatch) -> None:
    """A standby installs the leader's link-health state (ReplicaStatus
    fields 20-25): gauges and a mid-grace hysteresis position have no
    reset across a failover."""
    monkeypatch.setenv("TPUFT_LINK_RATIO", "3.0")
    monkeypatch.setenv("TPUFT_LINK_WARMUP_STEPS", "0")
    monkeypatch.setenv("TPUFT_LINK_GRACE_STEPS", "3")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    leader = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20,
    )
    standby = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20,
    )
    try:
        client = LighthouseClient(leader.address())
        client.heartbeat("a", step=1, state="step", link_send_gbps=1.0)
        client.heartbeat("b", step=1, state="step", link_send_gbps=1.0)
        client.heartbeat("b", step=2, state="step", link_send_gbps=0.1,
                         link_recv_gbps=0.2, link_hop_rtt_ms=42.0)
        assert leader.link_state("b") == 1  # mid-grace suspect
        leader.set_role(True, leader.address(), "", 1, 0)
        standby.set_role(False, leader.address(), "", 0, 0)
        snap = leader.snapshot()
        standby_client = LighthouseClient(standby.address())
        assert standby_client.replicate(snap).applied is True
        assert standby.link_state("b") == 1
        m = _scrape(standby)
        assert m['tpuft_link_send_gbps{replica="b"}'] == pytest.approx(0.1)
        assert m['tpuft_link_recv_gbps{replica="b"}'] == pytest.approx(0.2)
        assert m['tpuft_link_hop_rtt_ms{replica="b"}'] == pytest.approx(42.0)
    finally:
        leader.shutdown()
        standby.shutdown()


# ---------------------------------------------------------------------------
# obs: link_attribution + Perfetto hop track
# ---------------------------------------------------------------------------


def _summary(rid: str, ts: float, hops_flat: dict) -> dict:
    return {
        "event": "step_summary", "ts": ts, "replica_id": rid, "step": 1,
        "committed": True,
        "allreduce_lanes": {"lanes": 2, "topology": "ring",
                            "sent": [0], "recv": [0],
                            "hops": {"flat": hops_flat}},
    }


def _hops(hops, send, recv, comb, shape) -> dict:
    return {"hops": hops, "send_block_s": send, "recv_wait_s": recv,
            "combine_s": comb, "shape_s": shape}


def test_link_attribution_rollup_and_reset_awareness() -> None:
    from torchft_tpu.obs.report import link_attribution

    events = [
        _summary("a", 1.0, _hops(4, 2.0, 3.0, 0.5, 1.5)),
        _summary("a", 2.0, _hops(8, 4.0, 6.0, 1.0, 3.0)),
        # Counter reset (reconfigure): the epoch bank must keep the first
        # generation's 8-hop totals, not drop them.
        _summary("a", 3.0, _hops(2, 1.0, 1.5, 0.25, 0.75)),
    ]
    out = link_attribution(events)
    row = out["per_replica"]["a"]
    assert row["hops"] == 10  # 8 banked + 2 live
    assert row["shaping_s"] == pytest.approx(3.75)
    assert row["wire_s"] == pytest.approx(5.0 - 3.75)  # send_block - shaping
    assert row["stall_s"] == pytest.approx(7.5)
    assert row["combine_s"] == pytest.approx(1.25)
    frac = row["fractions"]
    assert sum(frac.values()) == pytest.approx(1.0, abs=1e-3)
    assert out["fractions"]["stall_s"] == pytest.approx(
        7.5 / (1.25 + 7.5 + 3.75 + 1.25), rel=1e-3
    )


def test_attribute_includes_link_attribution() -> None:
    from torchft_tpu.obs.report import attribute
    from torchft_tpu.obs.trace import synthetic_stream

    out = attribute(synthetic_stream())
    assert "link_attribution" in out
    assert "fractions" in out["link_attribution"]


def test_trace_renders_data_plane_hop_track() -> None:
    from torchft_tpu.obs.trace import (
        build_trace,
        synthetic_hop_stream,
        synthetic_stream,
        validate_trace,
    )

    events = synthetic_stream(n_replicas=2, steps=3)
    events += synthetic_hop_stream(n_replicas=2, steps=3)
    events.sort(key=lambda ev: ev["ts"])
    trace = build_trace(events)
    assert validate_trace(trace) == []
    dp_threads = [
        ev for ev in trace["traceEvents"]
        if ev.get("ph") == "M" and ev.get("name") == "thread_name"
        and " dp:" in str(ev.get("args", {}).get("name", ""))
    ]
    assert len(dp_threads) == 4  # 2 replicas x 2 lanes
    hop_slices = [ev for ev in trace["traceEvents"] if ev.get("cat") == "hop"]
    assert hop_slices
    assert {s["name"] for s in hop_slices} == {"hop:rs", "hop:ag"}
    # Hop slices live inside the replica's process (same pid as phases).
    phase_pids = {ev["pid"] for ev in trace["traceEvents"]
                  if ev.get("cat") == "phase"}
    assert {s["pid"] for s in hop_slices} <= phase_pids


def test_real_hop_records_roundtrip_through_trace(store, tmp_path) -> None:  # noqa: F811
    """Records from a REAL collective run dump/load/render end to end."""
    results = run_ranks(store, 2, lambda c, r: _one_allreduce(c, r, None))
    records = results[0]["records"]
    dump = {"replica_id": "g0:x", "records": records}
    path = tmp_path / "hops_g0.json"
    path.write_text(json.dumps(dump))
    from torchft_tpu.obs.trace import (
        build_trace,
        hops_to_stream,
        load_hops_dump,
        validate_trace,
    )

    stream = hops_to_stream(load_hops_dump(str(path)))
    trace = build_trace(stream)
    assert validate_trace(trace) == []
    assert any(ev.get("cat") == "hop" for ev in trace["traceEvents"])


# ---------------------------------------------------------------------------
# Unified worker /metrics endpoint
# ---------------------------------------------------------------------------


def test_worker_metrics_render_serve_and_sections(monkeypatch) -> None:
    from torchft_tpu.obs.prom import WorkerMetrics

    series = [
        ("tpuft_worker_step", "gauge", "step", (), 7),
        ("tpuft_worker_lane_sent_bytes_total", "counter", "bytes",
         (("tier", "flat"),), 123),
    ]
    wm = WorkerMetrics(replica_id="g0:x", provider=lambda: series)
    wm.add_section(lambda: "tpuft_semisync_rounds_total 3\n")
    text = wm.render_prometheus()
    assert 'tpuft_worker_step{replica="g0:x"} 7' in text
    assert ('tpuft_worker_lane_sent_bytes_total'
            '{replica="g0:x",tier="flat"} 123') in text
    assert "tpuft_semisync_rounds_total 3" in text
    # HELP/TYPE once per family.
    assert text.count("# TYPE tpuft_worker_step gauge") == 1
    port = wm.serve(port=0)
    try:
        assert port
        body = urllib.request.urlopen(
            f"http://[::1]:{port}/metrics", timeout=5
        ).read().decode()
        assert 'tpuft_worker_step{replica="g0:x"} 7' in body
    finally:
        wm.close()


def test_worker_metrics_port_env_serves_the_semisync_section(monkeypatch) -> None:
    """TPUFT_WORKER_METRICS_PORT is the one name for the worker's endpoint:
    set, it serves the registered ``tpuft_semisync_*`` section too; unset,
    nothing is served."""
    from torchft_tpu.obs import prom
    from torchft_tpu.semisync import SemiSyncMetrics

    monkeypatch.setenv("TPUFT_WORKER_METRICS_PORT", "0")
    semisync = SemiSyncMetrics(codec="int8", replica_id="g0")
    semisync.observe_round(True)
    wm = prom.WorkerMetrics(provider=lambda: [])
    port = wm.serve()
    try:
        assert port and wm.serving
        wm.add_section(semisync.render_prometheus)
        body = urllib.request.urlopen(
            f"http://[::1]:{port}/metrics", timeout=5
        ).read().decode()
        assert 'tpuft_semisync_rounds_total{replica="g0",codec="int8"} 1' in body
    finally:
        wm.close()
    monkeypatch.delenv("TPUFT_WORKER_METRICS_PORT", raising=False)
    wm2 = prom.WorkerMetrics(provider=lambda: [])
    assert wm2.serve() is None  # unset -> disabled


def test_manager_worker_metrics_endpoint_serves_link_gauges(
    store, monkeypatch  # noqa: F811
) -> None:
    monkeypatch.setenv("TPUFT_WORKER_METRICS_PORT", "0")
    client = MagicMock()
    client._quorum.return_value = make_quorum(max_world_size=2)
    client.should_commit.return_value = True
    manager, _, _ = make_manager(
        store, collective=_LaneStatsCollective(), client_mock=client
    )
    try:
        for _ in range(2):
            manager.start_quorum()
            manager.allreduce(np.full(16, 1.0, dtype=np.float32)).result()
            assert manager.should_commit()
        wm = manager.worker_metrics
        assert wm.serving
        text = wm.render_prometheus()
        assert "tpuft_worker_step" in text
        assert "tpuft_link_send_gbps" in text
        assert "tpuft_worker_step_time_ms_ewma" in text
    finally:
        manager.shutdown()


# ---------------------------------------------------------------------------
# Static registry greps (the test_flight.py convention)
# ---------------------------------------------------------------------------


def test_link_gauge_names_pinned_in_native_and_docs() -> None:
    lighthouse_cc = _read("native/src/lighthouse.cc")
    wire_md = _read("docs/wire.md")
    for gauge in (
        "tpuft_link_recv_gbps",
        "tpuft_link_send_gbps",
        "tpuft_link_hop_rtt_ms",
        "tpuft_link_slowness_ratio",
        "tpuft_link_state",
        "tpuft_links_degraded",
    ):
        assert gauge in lighthouse_cc, f"{gauge} not rendered by MetricsText"
        assert gauge in wire_md, f"{gauge} not documented in wire.md"


def test_hop_record_schema_pinned_against_native() -> None:
    """The cross-engine schema contract: ring.h declares RingHopRecord's
    fields in exactly HOP_RECORD_FIELDS order (the capi marshals 8 doubles
    positionally), and the native bindings emit exactly these keys."""
    ring_h = _read("native/src/ring.h")
    struct = ring_h.split("struct RingHopRecord")[1].split("};")[0]
    declared = re.findall(r"^\s+(?:double|int32_t|uint32_t|uint64_t)\s+(\w+)",
                          struct, re.M)
    assert tuple(declared) == HOP_RECORD_FIELDS
    native_py = _read("torchft_tpu/_native.py")
    hop_block = native_py.split("def hop_records")[1].split("def ")[0]
    for field in HOP_RECORD_FIELDS:
        assert f'"{field}"' in hop_block


def test_link_events_registered() -> None:
    from torchft_tpu.metrics import EVENTS

    for name in ("link_shaped", "link_alert", "hop"):
        assert name in EVENTS
    # The sentinel knobs documented in api.md.
    api_md = _read("docs/api.md")
    for knob in ("TPUFT_LINK_RATIO", "TPUFT_LINK_GRACE_STEPS",
                 "TPUFT_LINK_AUTO_DRAIN", "TPUFT_LINK_WARMUP_STEPS",
                 "TPUFT_HOP_SAMPLE", "TPUFT_HOP_RING",
                 "TPUFT_WORKER_METRICS_PORT", "TPUFT_HOP_DUMP_DIR"):
        assert knob in api_md, f"{knob} missing from api.md"
