"""Straggler-sentinel tests: the rolling step-time stats the Manager
computes, the heartbeat telemetry path, and the full wire-level sentinel
arc on the lighthouse — an injected-slow replica walks healthy -> suspect
-> straggler on /metrics, raises an alert on /alerts.json, and clears
after recovering (hysteresis both directions).
"""

import json
import time
import urllib.request

import pytest

from test_manager import make_manager, make_quorum, store  # noqa: F401
from unittest.mock import MagicMock

from torchft_tpu.obs.spans import StepTimeStats


# ---------------------------------------------------------------------------
# StepTimeStats
# ---------------------------------------------------------------------------


def test_step_time_stats_ewma_and_percentiles() -> None:
    stats = StepTimeStats(alpha=0.5, window=8)
    assert stats.ewma_ms == 0.0
    stats.observe(100.0)
    assert stats.ewma_ms == 100.0
    stats.observe(300.0)
    # 0.5 * 300 + 0.5 * 100
    assert stats.ewma_ms == pytest.approx(200.0)
    assert stats.last_ms == 300.0
    for _ in range(6):
        stats.observe(100.0)
    snap = stats.snapshot()
    assert snap["p50"] == 100.0
    assert snap["p99"] == 300.0
    assert snap["max"] == 300.0
    assert snap["n"] == 8
    # Window slides: after 8 more fast observations the slow outlier ages out.
    for _ in range(8):
        stats.observe(100.0)
    assert stats.snapshot()["max"] == 100.0
    # Negative observations are dropped, env-less defaults are sane.
    stats.observe(-5.0)
    assert stats.last_ms == 100.0
    assert 0.0 < StepTimeStats().alpha <= 1.0


def test_step_time_stats_env_knobs(monkeypatch) -> None:
    monkeypatch.setenv("TPUFT_STEP_TIME_ALPHA", "0.25")
    monkeypatch.setenv("TPUFT_STEP_TIME_WINDOW", "4")
    stats = StepTimeStats()
    assert stats.alpha == 0.25
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        stats.observe(v)
    assert stats.snapshot()["n"] == 5
    assert stats.snapshot()["max"] == 5.0  # window holds the last 4
    assert stats.snapshot()["p50"] == 4.0  # of [2, 3, 4, 5]: 1.0 has left it
    monkeypatch.setenv("TPUFT_STEP_TIME_ALPHA", "garbage")
    assert StepTimeStats().alpha == 0.5  # malformed knob falls back


# ---------------------------------------------------------------------------
# Manager: busy-time observation + telemetry push
# ---------------------------------------------------------------------------


def test_manager_observes_step_time_and_pushes_status(
    store, tmp_path, monkeypatch  # noqa: F811
) -> None:
    """Two committed steps: the second commit produces a busy-time
    observation (commit-to-commit wall minus FT waits), lands in the
    step_summary record, and rides the next SetStatus push."""
    metrics_path = tmp_path / "m.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(metrics_path))
    client = MagicMock()
    client._quorum.return_value = make_quorum(max_world_size=2)
    client.should_commit.return_value = True
    manager, _, _ = make_manager(store, client_mock=client)
    try:
        manager.start_quorum()
        assert manager.should_commit()
        time.sleep(0.05)  # deterministic lower bound on the step interval
        manager.start_quorum()
        assert manager.should_commit()

        events = [json.loads(l) for l in metrics_path.read_text().splitlines()]
        summaries = [e for e in events if e["event"] == "step_summary"]
        assert len(summaries) == 2
        assert "step_time_ms" not in summaries[0]  # first commit: no interval
        second = summaries[1]
        assert second["step_wall_ms"] >= 50.0
        assert 0.0 <= second["step_time_ms"] <= second["step_wall_ms"]
        assert second["step_time_ms_ewma"] > 0.0
        assert second["step_time_ms_p50"] >= 0.0
        assert second["step_time_ms_p99"] >= second["step_time_ms_p50"]

        # The (mocked) native ManagerServer saw the telemetry on the
        # post-commit status push.
        srv = manager._manager_server
        push = srv.set_status.call_args_list[-1].args
        assert push[0] == 2 and push[1] == "step"
        assert push[2] > 0.0  # ewma_ms
    finally:
        manager.shutdown()


def test_manager_failed_commit_skips_observation(
    store, tmp_path, monkeypatch  # noqa: F811
) -> None:
    """A failed commit produces no pacing observation, and the NEXT
    committed step doesn't either (its interval spans the failure)."""
    metrics_path = tmp_path / "m.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(metrics_path))
    client = MagicMock()
    client._quorum.return_value = make_quorum(max_world_size=2)
    client.should_commit.side_effect = [True, False, True]
    manager, _, _ = make_manager(store, client_mock=client)
    try:
        manager.start_quorum()
        assert manager.should_commit()
        manager.start_quorum()
        assert not manager.should_commit()
        manager.start_quorum()
        assert manager.should_commit()
        events = [json.loads(l) for l in metrics_path.read_text().splitlines()]
        summaries = [e for e in events if e["event"] == "step_summary"]
        assert len(summaries) == 3
        assert all("step_time_ms" not in s for s in summaries)
    finally:
        manager.shutdown()


def test_manager_server_set_status_step_time_reaches_metrics() -> None:
    """Native path: SetStatus telemetry rides the heartbeat into the
    lighthouse's tpuft_replica_step_time_seconds gauge."""
    from torchft_tpu._native import LighthouseServer, ManagerServer

    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200, quorum_tick_ms=20
    )
    manager = None
    try:
        manager = ManagerServer(
            replica_id="g7:tuuid",
            lighthouse_addr=lighthouse.address(),
            bind="127.0.0.1:0",
            heartbeat_interval_ms=25,
        )
        manager.set_status(3, "step", 123.5, 140.0)
        deadline = time.monotonic() + 5.0
        m = {}
        while time.monotonic() < deadline:
            m = _scrape(lighthouse)
            if m.get('tpuft_replica_step_time_seconds{replica="g7:tuuid"}'):
                break
            time.sleep(0.05)
        assert m[
            'tpuft_replica_step_time_seconds{replica="g7:tuuid"}'
        ] == pytest.approx(0.1235)
        # A phase push WITHOUT telemetry (0) must not wipe the gauge.
        manager.set_status(3, "quorum")
        time.sleep(0.2)
        m = _scrape(lighthouse)
        assert m[
            'tpuft_replica_step_time_seconds{replica="g7:tuuid"}'
        ] == pytest.approx(0.1235)
    finally:
        if manager is not None:
            manager.shutdown()
        lighthouse.shutdown()


# ---------------------------------------------------------------------------
# Wire-level sentinel arc
# ---------------------------------------------------------------------------


def _scrape(lighthouse) -> dict:
    port = lighthouse.http_address().rsplit(":", 1)[1]
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ) as resp:
        text = resp.read().decode()
    metrics = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_labels, _, value = line.rpartition(" ")
        metrics[name_labels] = float(value)
    return metrics


def _get_json(lighthouse, path: str) -> dict:
    port = lighthouse.http_address().rsplit(":", 1)[1]
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return json.loads(resp.read().decode())


def test_sentinel_arc_detects_and_recovers(monkeypatch) -> None:
    """The acceptance arc: an injected-slow replica transitions healthy ->
    suspect -> straggler on /metrics, its alert appears on /alerts.json,
    and the state clears (alert resolves) after it recovers — hysteresis
    in both directions, on per-step observations."""
    monkeypatch.setenv("TPUFT_STRAGGLER_RATIO", "1.5")
    monkeypatch.setenv("TPUFT_STRAGGLER_WARMUP_STEPS", "0")
    monkeypatch.setenv("TPUFT_STRAGGLER_GRACE_STEPS", "3")
    monkeypatch.setenv("TPUFT_STRAGGLER_AUTO_DRAIN", "0")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200, quorum_tick_ms=20
    )
    try:
        client = LighthouseClient(server.address())

        def hb(rid: str, step: int, ewma: float, last=None) -> None:
            client.heartbeat(
                rid, step=step, state="step",
                step_time_ms_ewma=ewma,
                step_time_ms_last=last if last is not None else ewma,
            )

        # Healthy lockstep pace.
        hb("0:fast", 1, 200.0)
        hb("1:slow", 1, 200.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:slow"}'] == 0
        assert m["tpuft_stragglers"] == 0

        # Injection: 3x the median.  First slow step -> suspect.
        hb("1:slow", 2, 600.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:slow"}'] == 1
        assert m['tpuft_replica_slowness_ratio{replica="1:slow"}'] == pytest.approx(
            3.0
        )
        assert m["tpuft_alerts_active"] == 0  # suspect alone never alerts

        # Grace steps over threshold -> straggler + alert.
        hb("0:fast", 2, 200.0)
        hb("1:slow", 3, 600.0)
        hb("1:slow", 4, 600.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:slow"}'] == 2
        assert m['tpuft_straggler_state{replica="0:fast"}'] == 0
        assert m["tpuft_stragglers"] == 1
        assert m["tpuft_alerts_active"] == 1
        alerts = _get_json(server, "/alerts.json")
        assert alerts["active"] == 1
        (alert,) = [a for a in alerts["alerts"] if a["active"]]
        assert alert["kind"] == "straggler"
        assert alert["replica_id"] == "1:slow"
        assert alert["ratio"] == pytest.approx(3.0)
        assert alert["resolved_ms"] == 0
        status = _get_json(server, "/status.json")
        assert status["straggler_state"]["1:slow"] == 2
        assert status["replica_step_time_ms"]["1:slow"] == 600
        assert status["replica_slowness"]["1:slow"] == pytest.approx(3.0)

        # A heartbeat WITHOUT a step advance is not an observation: the
        # grace budget counts steps, not heartbeats.
        hb("1:slow", 4, 600.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:slow"}'] == 2

        # Recovery needs the full grace of on-pace steps (hysteresis down).
        hb("1:slow", 5, 200.0)
        hb("1:slow", 6, 200.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:slow"}'] == 2  # 2 < grace
        hb("1:slow", 7, 200.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:slow"}'] == 0
        assert m["tpuft_alerts_active"] == 0
        alerts = _get_json(server, "/alerts.json")
        assert alerts["active"] == 0
        assert all(a["resolved_ms"] > 0 for a in alerts["alerts"])
    finally:
        server.shutdown()


def test_sentinel_suspect_is_cleared_by_one_good_step(monkeypatch) -> None:
    """A single on-pace step demotes a suspect (a blip is not a slow host) —
    and no alert ever raises."""
    monkeypatch.setenv("TPUFT_STRAGGLER_RATIO", "1.5")
    monkeypatch.setenv("TPUFT_STRAGGLER_WARMUP_STEPS", "0")
    monkeypatch.setenv("TPUFT_STRAGGLER_GRACE_STEPS", "3")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200, quorum_tick_ms=20
    )
    try:
        client = LighthouseClient(server.address())
        client.heartbeat("0:a", step=1, state="step", step_time_ms_ewma=200.0)
        client.heartbeat("1:b", step=1, state="step", step_time_ms_ewma=200.0)
        client.heartbeat("1:b", step=2, state="step", step_time_ms_ewma=600.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:b"}'] == 1
        client.heartbeat("1:b", step=3, state="step", step_time_ms_ewma=210.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:b"}'] == 0
        assert m["tpuft_alerts_active"] == 0
    finally:
        server.shutdown()


def test_sentinel_warmup_gate_suppresses_early_promotion(monkeypatch) -> None:
    """JIT warmup skews early busy times: an incarnation over the threshold
    from its first observations stays SUSPECT (no alert, no auto-drain)
    until past TPUFT_STRAGGLER_WARMUP_STEPS, then promotes on the first
    eligible observation if still slow."""
    monkeypatch.setenv("TPUFT_STRAGGLER_RATIO", "1.5")
    monkeypatch.setenv("TPUFT_STRAGGLER_GRACE_STEPS", "2")
    monkeypatch.setenv("TPUFT_STRAGGLER_WARMUP_STEPS", "5")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200, quorum_tick_ms=20
    )
    try:
        client = LighthouseClient(server.address())
        for step in range(1, 6):
            client.heartbeat("0:a", step=step, state="step",
                             step_time_ms_ewma=100.0)
            client.heartbeat("1:b", step=step, state="step",
                             step_time_ms_ewma=900.0)  # slow from birth
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:b"}'] == 1  # held at suspect
        assert m["tpuft_alerts_active"] == 0
        # First post-warmup observation, still slow: promotes.
        client.heartbeat("1:b", step=6, state="step", step_time_ms_ewma=900.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:b"}'] == 2
        assert m["tpuft_alerts_active"] == 1
    finally:
        server.shutdown()


def test_sentinel_auto_drain_rotates_straggler_out(monkeypatch) -> None:
    """TPUFT_STRAGGLER_AUTO_DRAIN=1: the alert marks the straggler draining
    (cooperative path) — but never below the min_replicas floor."""
    monkeypatch.setenv("TPUFT_STRAGGLER_RATIO", "1.5")
    monkeypatch.setenv("TPUFT_STRAGGLER_WARMUP_STEPS", "0")
    monkeypatch.setenv("TPUFT_STRAGGLER_GRACE_STEPS", "2")
    monkeypatch.setenv("TPUFT_STRAGGLER_AUTO_DRAIN", "1")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200, quorum_tick_ms=20
    )
    try:
        client = LighthouseClient(server.address())
        client.heartbeat("0:a", step=1, state="step", step_time_ms_ewma=200.0)
        client.heartbeat("1:b", step=1, state="step", step_time_ms_ewma=200.0)
        client.heartbeat("1:b", step=2, state="step", step_time_ms_ewma=800.0)
        client.heartbeat("1:b", step=3, state="step", step_time_ms_ewma=800.0)
        status = client.status()
        assert "1:b" in list(status.draining)
        alerts = _get_json(server, "/alerts.json")
        (alert,) = alerts["alerts"]
        assert alert["auto_drained"] is True
        # A draining replica's joins abort with the draining message, which
        # the Python Manager converts into a cooperative exit.  The exact
        # "is draining" token is the grep contract manager.py matches
        # (native wire errors are status + message, nothing structured).
        with pytest.raises(RuntimeError, match="is draining"):
            client.quorum("1:b", timeout_ms=2000, step=3)
    finally:
        server.shutdown()


def test_sentinel_sole_survivor_clears_straggler_state(monkeypatch) -> None:
    """A flagged straggler whose last peer dies must still be able to clear
    its state: with fewer than two reporters slowness is unscorable, so
    observations count toward recovery instead of freezing the state
    machine (and the alert) forever."""
    monkeypatch.setenv("TPUFT_STRAGGLER_RATIO", "1.5")
    monkeypatch.setenv("TPUFT_STRAGGLER_WARMUP_STEPS", "0")
    monkeypatch.setenv("TPUFT_STRAGGLER_GRACE_STEPS", "2")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200, quorum_tick_ms=20
    )
    try:
        client = LighthouseClient(server.address())
        client.heartbeat("0:a", step=1, state="step", step_time_ms_ewma=200.0)
        client.heartbeat("1:b", step=1, state="step", step_time_ms_ewma=200.0)
        client.heartbeat("1:b", step=2, state="step", step_time_ms_ewma=800.0)
        client.heartbeat("1:b", step=3, state="step", step_time_ms_ewma=800.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:b"}'] == 2
        # The only peer dies; the survivor keeps stepping at any pace.
        assert server.evict("0") == 1
        client.heartbeat("1:b", step=4, state="step", step_time_ms_ewma=800.0)
        client.heartbeat("1:b", step=5, state="step", step_time_ms_ewma=800.0)
        m = _scrape(server)
        assert m['tpuft_straggler_state{replica="1:b"}'] == 0
        assert m["tpuft_alerts_active"] == 0
    finally:
        server.shutdown()


def test_sentinel_auto_drain_respects_min_replicas(monkeypatch) -> None:
    monkeypatch.setenv("TPUFT_STRAGGLER_RATIO", "1.5")
    monkeypatch.setenv("TPUFT_STRAGGLER_WARMUP_STEPS", "0")
    monkeypatch.setenv("TPUFT_STRAGGLER_GRACE_STEPS", "2")
    monkeypatch.setenv("TPUFT_STRAGGLER_AUTO_DRAIN", "1")
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=200, quorum_tick_ms=20
    )
    try:
        client = LighthouseClient(server.address())
        client.heartbeat("0:a", step=1, state="step", step_time_ms_ewma=200.0)
        client.heartbeat("1:b", step=1, state="step", step_time_ms_ewma=200.0)
        client.heartbeat("1:b", step=2, state="step", step_time_ms_ewma=800.0)
        client.heartbeat("1:b", step=3, state="step", step_time_ms_ewma=800.0)
        # Alert raised, but draining would leave 1 < min_replicas=2: skip.
        alerts = _get_json(server, "/alerts.json")
        assert alerts["active"] == 1
        assert alerts["alerts"][0]["auto_drained"] is False
        status = client.status()
        assert list(status.draining) == []
        # Capacity recovers (a third replica joins): the NEXT straggler
        # observation retries the rotation — "never below the floor" means
        # deferred, not abandoned.
        client.heartbeat("2:c", step=1, state="step", step_time_ms_ewma=200.0)
        client.heartbeat("1:b", step=4, state="step", step_time_ms_ewma=800.0)
        status = client.status()
        assert "1:b" in list(status.draining)
        alerts = _get_json(server, "/alerts.json")
        assert alerts["alerts"][0]["auto_drained"] is True
    finally:
        server.shutdown()
