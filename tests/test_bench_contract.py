"""Guards the contracts between bench.py and the code it measures.

The kill-goodput benchmark counts committed work and verified heals by
grepping subprocess logs (bench.py) for strings emitted by
examples/train_ddp.py and torchft_tpu/manager.py.  Nothing else ties those
strings together — a log-format tweak would silently zero the headline
metric — so this test pins all three ends of the contract, and bench.py's
structural selftest catches signature drift between its scenario functions
(the exact failure that cost round 2 its numbers).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(relpath: str) -> str:
    with open(os.path.join(REPO, relpath), "r", encoding="utf-8") as f:
        return f.read()


def test_bench_greps_match_emitters() -> None:
    bench = _read("bench.py")
    example = _read(os.path.join("examples", "train_ddp.py"))
    manager = _read(os.path.join("torchft_tpu", "manager.py"))

    # Primary contract: bench counts the Manager's structured metrics
    # events — the emitter and the consumer must name the same events.
    assert '"commit"' in bench and '"heal_fetched"' in bench
    assert '"commit",' in manager and '"heal_fetched"' in manager

    # Fallback contract: bench greps these literals from the logs...
    assert 'b"committed=True"' in bench
    # ...which the example emits as an f-string ending in the bool repr.
    assert "committed={committed}" in example

    # bench.py verifies the heal ran by this literal...
    assert 'b"healing from replica"' in bench
    # ...which the Manager logs on the recovery-destination path.
    assert '"healing from replica' in manager


def test_transfer_quick_smoke() -> None:
    """bench_transfer --quick in-process: the striped multi-donor fetch and
    mid-fetch donor-kill failover must work on a small dict — transfer-path
    regressions fail tier-1 here instead of only showing up in
    BENCH_*.json artifacts."""
    sys.path.insert(0, REPO)
    try:
        import bench_transfer
    finally:
        sys.path.pop(0)
    payload = bench_transfer.run_quick(gb=0.008, buffers=8)
    assert payload["failover_completed"]
    results = {(r["donors"], r["donor_killed_mid_fetch"]): r for r in payload["results"]}
    assert set(results) == {(1, False), (2, False), (2, True)}
    for r in results.values():
        assert r["fetch_s"] > 0 and r["fetch_gb_per_s"] > 0


def test_ha_quick_smoke() -> None:
    """bench_ha --quick in-process: 2 HA lighthouse replicas, 2 replica
    groups, one SIGKILL of the active leader mid-run.  The tier-1 gate on
    the whole failover arc: quorum formation resumes within one lease
    period, ZERO failed commits on the healthy groups, /metrics +
    straggler-sentinel continuity on the new leader at epoch+1, the
    surviving standby (none in quick mode) never dual-serves, and the
    takeover lands in the obs stream — control-plane HA regressions fail
    here instead of only showing up in HA_BENCH.json."""
    sys.path.insert(0, REPO)
    try:
        import bench_ha
    finally:
        sys.path.pop(0)
    payload = bench_ha.run_quick()
    # Schema contract: the keys the full HA_BENCH.json artifact is built
    # from (bench.py --scenario lighthouse-failover writes the same dict).
    for key in (
        "quick", "lighthouses", "groups", "lease_ms", "takeover_s",
        "leader_epoch_before", "leader_epoch_after", "resume_gap_s",
        "max_resume_gap_s", "resume_budget_s", "resumed_within_lease",
        "failed_commits_healthy_groups", "metrics_continuity_ok",
        "failover_event_seen", "failover_event_epoch", "worker_summaries",
        "per_group_commits", "standby_roles_after", "ok",
    ):
        assert key in payload, f"HA_BENCH schema missing {key}"
    assert payload["quick"] is True
    assert payload["takeover_s"] is not None and payload["takeover_s"] > 0
    assert payload["leader_epoch_after"] == payload["leader_epoch_before"] + 1
    assert payload["resumed_within_lease"], payload
    # The headline criterion: no healthy replica group failed a commit
    # because the control plane failed over.
    assert payload["failed_commits_healthy_groups"] == 0, payload
    assert payload["metrics_continuity_ok"], payload
    assert payload["failover_event_seen"]
    assert payload["failover_event_epoch"] == payload["leader_epoch_after"]
    for summary in payload["worker_summaries"]:
        assert summary["commits"] > 0 and summary["failed"] == 0
    assert payload["ok"], payload


def test_scale_quick_smoke() -> None:
    """bench_scale --quick in-process: the O(100)-group scale harness's
    tier-1 gate.  A 4-rank topology-parity check (ring2d active, results
    within tolerance of the flat ring, replica-consistent bitwise, int
    payloads uncompressed) plus a 4-group control cell under a pinned
    ring2d topology with a 2-victim correlated preemption wave: the
    surviving half reforms a quorum and keeps committing (the post-wave
    2-group world crosses the auto crossover back to the flat ring), the
    lighthouse flight-recorder dump reconstructs the wave's quorum
    transitions, and the cell leaks zero fds — so the full SCALE_BENCH
    sweep can stay marked slow without losing CI coverage."""
    sys.path.insert(0, REPO)
    try:
        import bench_scale
    finally:
        sys.path.pop(0)
    payload = bench_scale.run_quick()
    # Schema contract: the keys the full SCALE_BENCH.json artifact is
    # built from (bench.py --scenario scale writes the same cell dicts).
    for key in ("metric", "quick", "parity", "cells", "dataplane",
                "fd_leaked_total", "ok"):
        assert key in payload, f"SCALE_BENCH schema missing {key}"
    assert payload["quick"] is True
    parity = payload["parity"]
    for key in ("ring2d_active", "int_bypass_ok", "replica_consistent",
                "topologies_close", "ok"):
        assert parity[key] is True, (key, parity)
    (cell,) = payload["cells"]
    for key in ("groups", "wave", "min_replicas", "warmed_groups",
                "worker_summaries", "survivor_failed_commits",
                "per_group_commits", "quorum_reformed", "wave_reconstructed",
                "quorum_formation", "heartbeat_fanin", "scrape", "rpc",
                "flight_dump_found", "fd_leaked", "ok"):
        assert key in cell, f"scale cell schema missing {key}"
    assert cell["warmed_groups"] == cell["groups"] == 4
    assert cell["quorum_reformed"], cell
    assert cell["wave_reconstructed"], cell
    assert cell["flight_dump_found"]
    # Zero leaked sockets/fds across the whole cell (driver-side).
    assert cell["fd_leaked"] == 0, cell
    assert payload["fd_leaked_total"] == 0
    # The PR 7 histograms carried real observations.
    assert cell["quorum_formation"]["count"] > 0
    assert cell["heartbeat_fanin"]["count"] > 0
    assert cell["rpc"]["Quorum"]["count"] > 0
    assert payload["ok"], payload


def test_allreduce_quick_smoke() -> None:
    """bench_allreduce --quick in-process: the striped multi-lane ring (1
    vs 2 lanes) and the pipelined-vs-monolithic bucket paths must complete
    and commit on a small dict — data-plane regressions fail tier-1 here
    instead of only showing up in ALLREDUCE_BENCH.json."""
    sys.path.insert(0, REPO)
    try:
        import bench_allreduce
    finally:
        sys.path.pop(0)
    payload = bench_allreduce.run_quick()
    # Schema contract: the keys the full bench artifact is built from.
    assert payload["quick"] is True
    assert {r["lanes"] for r in payload["lanes"]} == {1, 2}
    for r in payload["lanes"]:
        assert r["gb_per_s"] > 0 and r["wall_s"] > 0
        assert len(r["lane_bytes_sent"]) == r["lanes"]
        assert all(b > 0 for b in r["lane_bytes_sent"])
    modes = {r["mode"]: r for r in payload["e2e"]}
    assert set(modes) == {"pipelined", "monolithic"}
    for r in modes.values():
        assert r["committed"] == r["steps"]  # healthy run: every step lands
        assert r["steps_per_s"] > 0
    # The pipelined path must never commit less than the monolithic one.
    assert payload["pipelined_commits_ok"]


def test_ring_engine_quick_smoke() -> None:
    """Ring-engine tier-1 gate: one small ``bench_allreduce --engine both``
    cell live (py + native at the same unshaped-loopback config, plus the
    live bitwise parity pin), and the committed ALLREDUCE_BENCH.json
    artifact must carry the engine A/B schema — engine field on every lane
    record, native loopback >= py loopback, parity flag true."""
    sys.path.insert(0, REPO)
    try:
        import bench_allreduce
    finally:
        sys.path.pop(0)
    payload = bench_allreduce.run_engine_quick(
        payload_mb=4.0, lanes=2, trials=2
    )
    assert payload["native_available"] is True
    by_engine = {c["engine"]: c for c in payload["cells"]}
    assert set(by_engine) == {"py", "native"}
    for cell in by_engine.values():
        assert cell["gb_per_s"] > 0 and cell["wall_s"] > 0
        assert len(cell["lane_bytes_sent"]) == cell["lanes"]
    # Same config, same wire bytes: the engine is a pure hot-loop swap.
    assert (by_engine["py"]["lane_bytes_sent"]
            == by_engine["native"]["lane_bytes_sent"])
    assert payload["parity_bitwise"] is True
    assert payload["native_loopback_ok"], payload["native_loopback_speedup"]

    # The committed artifact carries the regenerated engine A/B.
    import json as _json

    with open(os.path.join(REPO, "ALLREDUCE_BENCH.json")) as f:
        artifact = _json.load(f)
    lane_records = [
        r for r in artifact["results"] if r.get("section") == "lanes"
    ]
    assert lane_records, "no lane records in ALLREDUCE_BENCH.json"
    assert all(r.get("engine") in ("py", "native") for r in lane_records)
    assert {r["engine"] for r in lane_records} == {"py", "native"}
    summary = artifact["summary"]
    loopback = summary["engine_loopback_gb_per_s"]
    assert loopback["native"] >= loopback["py"]
    assert summary["native_loopback_speedup"] >= 1.0
    assert summary["engine_parity_bitwise"] is True


def test_transport_quick_smoke() -> None:
    """Same-host transport tier-1 gate: one live shm-vs-tcp A/B cell
    (bench_allreduce.run_transport_quick), the bitwise transport-parity
    pin, the one-call multi-stripe pin (one Python<->native crossing per
    allreduce, call count asserted), and the committed
    ALLREDUCE_BENCH.json transport schema.  The shm >= tcp throughput
    gate applies only on multi-core hosts: on a single core both
    transports bottleneck on scheduler alternation and the ratio is
    noise around 1.0 (the cell records cpu_count for exactly this)."""
    sys.path.insert(0, REPO)
    try:
        import bench_allreduce
    finally:
        sys.path.pop(0)

    payload = bench_allreduce.run_transport_quick(
        payload_mb=4.0, lanes=2, trials=2
    )
    by_transport = {c["transport"]: c for c in payload["cells"]}
    assert set(by_transport) == {"tcp", "shm"}
    for cell in by_transport.values():
        assert cell["gb_per_s"] > 0 and cell["wall_s"] > 0
    # Same frames either way: the transport is a pure data-plane swap.
    assert (by_transport["tcp"]["lane_bytes_sent"]
            == by_transport["shm"]["lane_bytes_sent"])
    assert payload["parity_bitwise"] is True
    assert payload["shm_speedup"] > 0
    if (payload.get("cpu_count") or 1) > 1:
        assert payload["shm_ok"], payload["shm_speedup"]
    ms = payload["multi_stripe"]
    if ms is not None:  # native engine present
        assert ms["stripes_per_op"] > 1
        assert ms["pass_calls"] == ms["ops"], ms
        assert ms["one_call_per_op"] is True

    # The committed artifact carries the transport A/B + multi-stripe cell.
    with open(os.path.join(REPO, "ALLREDUCE_BENCH.json")) as f:
        artifact = json.load(f)
    transport_records = [
        r for r in artifact["results"] if r.get("section") == "transport"
    ]
    assert transport_records, "no transport cell in ALLREDUCE_BENCH.json"
    rec = transport_records[0]
    assert {c["transport"] for c in rec["cells"]} == {"tcp", "shm"}
    assert rec["parity_bitwise"] is True
    assert rec["multi_stripe"]["one_call_per_op"] is True
    summary = artifact["summary"]
    assert summary["transport_parity_bitwise"] is True
    assert summary["shm_speedup"] > 0
    assert summary["multi_stripe_one_call_per_op"] is True


def test_parity_matrix_axes_static_audit() -> None:
    """Static audit of the engine parity matrix's axis coverage: the
    bitwise pin in tests/test_ring_engine.py must exercise every codec
    the wire supports (f32 raw / bf16 / int8 / int4) and both lane
    transports (tcp / shm) — an axis silently dropped from the live
    matrix would let a codec or transport drift off the parity contract
    without any test going red."""
    with open(os.path.join(REPO, "tests", "test_ring_engine.py")) as f:
        src = f.read()
    run_ring = src.split("def _run_ring")[1].split("\ndef ")[0]
    # Codec axis: every wire codec appears in the shared ring driver.
    assert 'allow_wire_compression=False' in run_ring  # f32 raw framing
    assert 'wire_dtype="bf16"' in run_ring
    assert 'wire_codec="int8"' in run_ring
    assert 'wire_codec="int4"' in run_ring
    # Transport axis: the driver is transport-aware and a live test pins
    # both transports bitwise for both engines.
    assert "transport" in run_ring
    assert "def test_transport_axis_parity_bitwise" in src
    transport_test = src.split(
        "def test_transport_axis_parity_bitwise"
    )[1].split("\ndef ")[0]
    assert '("tcp", "shm")' in transport_test
    assert '("py", "native")' in transport_test
    # Engine + topology axes: the original matrix still parametrizes both.
    assert "def test_engine_parity_bitwise" in src
    assert '"ring2d"' in src


def test_ec_quick_smoke() -> None:
    """Erasure-coded healing tier-1 gate (bench_transfer.run_ec_quick at a
    small state size): the encode-overhead cell must show the donor-side
    encode off the train-thread critical path, the reconstruction cell
    must be BITWISE-equal to the donor stream, the SIGKILLed-donor-set
    wave must reconstruct from surviving shard holders, and the
    manager-level prefer-mode wave must heal with zero survivor failed
    commits.  Also pins the committed TRANSFER_BENCH.json artifact schema
    for the same cells."""
    sys.path.insert(0, REPO)
    try:
        import bench_transfer
    finally:
        sys.path.pop(0)
    payload = bench_transfer.run_ec_quick(gb=0.008, buffers=8)
    cells = {c["op"]: c for c in payload["ec"]}
    assert set(cells) == {"ec_encode", "ec_reconstruct", "ec_wave",
                          "ec_manager_wave"}
    # Donor-side overhead: the train thread must not pay for the encode
    # (generous bound — CI hosts are noisy; the pinned artifact number is
    # the honest one).
    assert cells["ec_encode"]["overhead_ratio"] < 1.25
    assert cells["ec_encode"]["encode_pipeline_s"] >= 0
    assert cells["ec_reconstruct"]["bitwise"] is True
    assert cells["ec_reconstruct"]["reconstruct_s"] > 0
    wave = cells["ec_wave"]
    assert wave["ok"] and wave["donor_fetch_failed"] and wave["bitwise"]
    assert wave["donors_sigkilled"] >= 2
    mwave = cells["ec_manager_wave"]
    assert mwave["ok"], mwave
    # The heal path never touches survivors in prefer mode; the SIGKILL
    # itself racing mid-allreduce may fail ONE survivor round (the same
    # one-failed-round cost every crash pays) — the live smoke budgets
    # that, the pinned artifact below stays strict at zero.
    assert mwave["survivor_failed_commits"] <= 1
    assert mwave["ec_reconstructions"] >= 1
    assert mwave["victim_post_heal_commits"] > 0

    # The committed artifact carries the same cell set at the pinned size.
    import json as _json

    with open(os.path.join(REPO, "TRANSFER_BENCH.json")) as f:
        artifact = _json.load(f)
    ops = {r.get("op") for r in artifact.get("results", [])}
    assert {"ec_encode", "ec_reconstruct", "ec_wave", "ec_manager_wave"} <= ops
    art = {r["op"]: r for r in artifact["results"] if "op" in r}
    assert art["ec_reconstruct"]["bitwise"] is True
    assert art["ec_wave"]["ok"] is True
    assert art["ec_manager_wave"]["survivor_failed_commits"] == 0
    assert artifact["summary"]["ec"]["encode_overhead_ratio"] < 1.05


def test_link_quick_smoke() -> None:
    """Slow-link sentinel tier-1 gate (bench_allreduce.run_link quick
    cell): with ONE peer's outbound link re-shaped 10x slower mid-run (no
    reconfigure — invisible to heartbeat timeouts and to the straggler
    sentinel's wall-minus-waits signal), the lighthouse raises a slow_link
    alert within a bounded number of victim commit rounds, names the
    victim as the reporting sender, the healthy control run raises ZERO
    link alerts, the attribution split's fractions sum to ~1 with the
    ADDED wall landing on the wire/shaping/stall side, and the hop
    recorder's overhead stays inside a generous live bound (the committed
    artifact pins the honest number)."""
    sys.path.insert(0, REPO)
    try:
        import bench_allreduce
    finally:
        sys.path.pop(0)
    r = bench_allreduce.run_link(quick=True)
    assert r["ok"], r
    assert r["detected"] is True
    assert r["detection_rounds"] is not None and r["detection_rounds"] <= 10
    assert r["alert_src_is_victim"] is True
    assert r["healthy"]["link_alerts"] == 0
    assert r["degraded"]["link_alerts"] >= 1
    # Every group of both cells committed every round: a degraded link is
    # slow, not broken — no failed commits, which is exactly why only the
    # sentinel can see it.
    assert all(f == 0 for f in r["healthy"]["failed"])
    assert all(f == 0 for f in r["degraded"]["failed"])
    assert r["attribution_fraction_sum"] == pytest.approx(1.0, abs=0.01)
    assert r["added_wire_stall_fraction"] is not None
    assert r["added_wire_stall_fraction"] >= 0.9
    # The victim's sampled hop timeline must bracket the injected fault
    # window: records before AND after the mid-run re-shaping, so the
    # post-mortem black box covers the moment that matters.
    assert r["hop_timeline_records"] > 0
    assert r["hop_timeline_brackets_fault"] is True
    # Hop-recorder cost guard, live (noisy-CI bound; artifact is strict).
    assert r["overhead"]["impact"] is not None
    assert r["overhead"]["impact"] < 1.35

    # The committed artifact carries the full-size cell with strict gates.
    with open(os.path.join(REPO, "ALLREDUCE_BENCH.json")) as f:
        artifact = json.load(f)
    link = artifact.get("link")
    assert link, "ALLREDUCE_BENCH.json is missing the link cell"
    assert link["ok"] is True
    assert link["detected"] is True
    assert link["detection_rounds"] <= 8
    assert link["alert_src_is_victim"] is True
    assert link["healthy"]["link_alerts"] == 0
    assert link["attribution_fraction_sum"] == pytest.approx(1.0, abs=0.01)
    assert link["added_wire_stall_fraction"] >= 0.9
    assert link["overhead"]["impact"] < 1.02  # the <2% recorder budget


def test_peer_kill_hop_timeline_brackets_fault() -> None:
    """Mid-allreduce peer-kill cell: beyond the existing latch/rebuild
    gates, the surviving group's hop timeline must BRACKET the kill —
    pre-fault hops banked when abort() tore the generation down, plus
    hops from the rebuilt lanes.  A timeline that only covers one side
    of the fault window is useless as a black box.

    The cell injects the kill on a 0.3 s wall timer against a shaped
    16 MB allreduce; on a loaded 1-core host that race occasionally
    mis-lands (timer after drain, or recovery outrunning a gate), so the
    trial retries like the other timing-shaped smokes — the contract is
    that a CLEAN run brackets the fault, not that the scheduler never
    starves the timer."""
    sys.path.insert(0, REPO)
    try:
        import bench_allreduce
    finally:
        sys.path.pop(0)
    r = None
    for _ in range(3):
        r = bench_allreduce.bench_peer_kill(lanes=2)
        if r["ok"]:
            break
    assert r["ok"], r
    assert r["hop_timeline_records"] > 0
    assert r["hop_timeline_brackets_fault"] is True
    assert r["kill_ts"] is not None


def test_device_prep_quick_smoke() -> None:
    """Device-resident wire prep e2e gate: a small 2-group run with the
    on-device bf16 cast (and the sharded fetch, which engages under the
    suite's forced multi-device platform) must commit at least as many
    steps as the host-cast reference, halve the D2H fetch bytes, and emit
    the byte fields the ALLREDUCE_BENCH artifact schema quotes."""
    sys.path.insert(0, REPO)
    try:
        import bench_allreduce
    finally:
        sys.path.pop(0)
    trials = {
        mode: bench_allreduce.bench_e2e(
            lanes=2, pipelined=True, steps=2, grads_mb=1.0, n_leaves=4,
            mbps=0.0, rtt_ms=0.0, bucket_mb=0.5, timeout_s=60.0,
            procs=False, device_prep=prep, sharded=shard, wire_dtype="bf16",
        )
        for mode, (prep, shard) in {
            "host": (False, False),
            "prep": (True, False),
            "sharded": (True, True),
        }.items()
    }
    for name, r in trials.items():
        # Schema contract for the new artifact fields.
        for field in ("d2h_bytes", "h2d_bytes", "wire_bytes", "fetch_slices",
                      "device_prep", "sharded_fetch", "wire_dtype"):
            assert field in r, (name, field)
        assert r["committed"] == r["steps"], name
        assert r["d2h_bytes"] > 0 and r["wire_bytes"] > 0
    assert trials["prep"]["committed"] >= trials["host"]["committed"]
    assert trials["sharded"]["committed"] >= trials["host"]["committed"]
    # The headline: device-side bf16 cast halves the fetch bytes.
    ratio = trials["host"]["d2h_bytes"] / trials["prep"]["d2h_bytes"]
    assert 1.9 <= ratio <= 2.1, ratio
    import jax

    if len(jax.local_devices()) > 1:
        assert trials["sharded"]["fetch_slices"] > 0


def test_diloco_quick_smoke() -> None:
    """bench_diloco --quick in-process: 2 replica groups, small model,
    shaped 60 ms-RTT link.  The tier-1 gate on the streaming semi-sync
    plane: inner-step throughput with a CONCURRENT background fragment
    sync must meet or beat the blocking port's (whose whole-round stall is
    measured alongside), both cells must commit every round, the int8+EF
    wire must cost <= 0.27x the f32 wire, and error feedback must bound
    the drift plain int8 accumulates — plus the DILOCO_BENCH.json schema
    the full artifact is built from."""
    sys.path.insert(0, REPO)
    try:
        import bench_diloco
    finally:
        sys.path.pop(0)
    payload = bench_diloco.run_quick()
    # Schema contract: the keys the full DILOCO_BENCH.json artifact is
    # built from (bench.py --scenario diloco writes the same dict).
    for key in ("metric", "quick", "overlap", "quant", "ok"):
        assert key in payload, f"DILOCO_BENCH schema missing {key}"
    assert payload["quick"] is True
    overlap = payload["overlap"]
    for key in ("link", "cells", "inner_throughput_ratio_streaming_vs_nosync",
                "inner_throughput_ratio_blocking_vs_nosync",
                "streaming_within_5pct", "streaming_beats_blocking",
                "blocking_stall_ms_per_round", "streaming_stall_ms_per_round"):
        assert key in overlap, f"overlap schema missing {key}"
    cells = overlap["cells"]
    assert set(cells) == {"nosync", "blocking", "streaming"}
    for name in ("blocking", "streaming"):
        # Healthy run: every timed round committed, and the state actually
        # fragmented + rode the wire.
        assert cells[name]["committed_rounds"] == overlap["rounds"], cells[name]
        assert cells[name]["fragments"] >= 2
        assert cells[name]["wire_bytes"] > 0
    # The headline gate quick mode enforces: a concurrent outer sync must
    # not make inner throughput WORSE than the blocking baseline.
    assert overlap["streaming_beats_blocking"], overlap
    quant = payload["quant"]
    for key in ("drift_vs_f32", "ef_bounds_drift", "wire_ratio_int8",
                "wire_ratio_ok"):
        assert key in quant, f"quant schema missing {key}"
    assert set(quant["drift_vs_f32"]) == {"bf16", "int8", "int8_noef"}
    assert quant["ef_bounds_drift"], quant
    assert quant["wire_ratio_int8"] <= 0.27, quant
    # The 4-bit cell rides in its own keys (the drift_vs_f32 key set above
    # is a pinned contract): packed wire <= 0.14x f32, EF bounds the
    # no-EF drift, and the EF drift sits at the 127/7 step-ratio floor
    # relative to int8 (no accumulation blowup).
    assert set(quant["int4_drift_vs_f32"]) == {"int4", "int4_noef"}
    assert quant["int4_ef_bounds_drift"], quant
    assert quant["int4_drift_at_step_ratio_floor"], quant
    assert quant["wire_ratio_int4"] <= 0.14, quant
    assert payload["ok"], payload


def test_elastic_quick_smoke() -> None:
    """bench_elastic --quick in-process: a 3-group spot-market trace
    (leave/join/leave over cooperative drain notices) scored against a
    fixed-size oracle.  The tier-1 gate on the elastic tentpole: goodput
    within the oracle gate, ZERO failed survivor commits across every
    transition, constant global batch in every committed step record,
    incremental lane reconfiguration engaged, proactive EC re-shard on
    membership change, and no leaked fds — plus the ELASTIC_BENCH.json
    schema the full artifact is built from."""
    sys.path.insert(0, REPO)
    try:
        import bench_elastic
    finally:
        sys.path.pop(0)
    payload = bench_elastic.run_quick()
    # Schema contract: the keys the full ELASTIC_BENCH.json artifact is
    # built from (bench.py --scenario elastic writes the same dict).
    for key in ("metric", "quick", "seed", "global_batch", "elastic",
                "oracle", "goodput_ratio_vs_oracle", "goodput_gate",
                "dead_time_baseline_s", "max_transition_dead_s",
                "survivor_failed_commits", "constant_global_batch",
                "fd_leaked_total", "crossover_exercised", "ok"):
        assert key in payload, f"ELASTIC_BENCH schema missing {key}"
    assert payload["quick"] is True
    cell = payload["elastic"]
    for key in ("committed_steps", "membership_changes", "reconfigure_modes",
                "ec_reshard_pushes", "elastic_records", "transitions",
                "transitions_stabilized", "survivor_failed_commits",
                "max_transition_dead_s", "fd_leaked", "ok"):
        assert key in cell, f"elastic cell schema missing {key}"
    assert payload["goodput_ratio_vs_oracle"] >= payload["goodput_gate"], payload
    # The headline criteria: departures are notice-driven, so NO survivor
    # ever fails a commit, and the batch engine holds the global batch
    # constant through every membership size it saw.
    assert payload["survivor_failed_commits"] == 0, payload
    assert payload["constant_global_batch"] is True, payload
    assert payload["max_transition_dead_s"] < payload["dead_time_baseline_s"]
    assert payload["fd_leaked_total"] == 0
    assert cell["membership_changes"] > 0
    assert cell["reconfigure_modes"].get("incremental", 0) > 0, cell
    assert cell["ec_reshard_pushes"] > 0, cell
    assert cell["elastic_records"]["committed_with_plan"] > 0
    assert len(cell["elastic_records"]["participants_seen"]) >= 2
    assert payload["ok"], payload

    # The committed full-trace artifact carries the strict gates plus the
    # ring2d<->ring crossover pin quick mode cannot exercise.
    with open(os.path.join(REPO, "ELASTIC_BENCH.json")) as f:
        artifact = json.load(f)
    assert artifact["metric"] == "elastic_goodput_vs_oracle"
    assert artifact["quick"] is False
    assert artifact["goodput_ratio_vs_oracle"] >= artifact["goodput_gate"]
    assert artifact["survivor_failed_commits"] == 0
    assert artifact["constant_global_batch"] is True
    assert artifact["max_transition_dead_s"] < artifact["dead_time_baseline_s"]
    assert artifact["crossover_exercised"] is True
    assert artifact["elastic"]["reconfigure_modes"].get("incremental", 0) > 0
    assert artifact["elastic"]["ec_reshard_pushes"] > 0
    assert artifact["ok"] is True


def test_bench_chip_paths_refuse_anything_but_a_known_tpu() -> None:
    """The chip measurements never run on the CPU under a device's name, and
    an MFU is never computed against a guessed peak: no TPU is an error (in
    the child that owns the chip, so `main` fails with it), and so is a
    device kind the peaks table does not hold."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)

    with pytest.raises(RuntimeError, match="measures the chip"):
        bench.tpu_device()

    class Device:
        device_kind = "TPU v5 lite"

    assert bench._peak_flops(Device()) == 197e12
    Device.device_kind = "TPU v99 imaginary"
    with pytest.raises(RuntimeError, match="no bf16 peak recorded"):
        bench._peak_flops(Device())

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--chip", "large"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0 and "measures the chip" in out.stderr


def test_bench_selftest() -> None:
    """bench.py --selftest verifies its own scenario-call signatures without
    touching the chip or spawning training subprocesses."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--selftest"],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    assert "bench selftest ok" in out.stdout


def test_example_emits_committed_line(tmp_path) -> None:
    """Runs the example app for a couple of steps in a subprocess (tiny
    model, CPU platform, 1 replica group) and asserts the exact log line the
    kill-bench greps for appears — the runtime end of the string contract."""
    from torchft_tpu._native import LighthouseServer

    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200
    )
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "TPUFT_LIGHTHOUSE": lighthouse.address(),
            "REPLICA_GROUP_ID": "0",
            "NUM_REPLICA_GROUPS": "1",
            "MASTER_ADDR": "localhost",
        }
    )
    try:
        out = subprocess.run(
            [
                sys.executable,
                os.path.join(REPO, "examples", "train_ddp.py"),
                "--steps",
                "2",
            ],
            capture_output=True,
            text=True,
            timeout=300,
            cwd=REPO,
            env=env,
        )
    finally:
        lighthouse.shutdown()
    assert out.returncode == 0, out.stdout + out.stderr
    assert "committed=True" in out.stdout


def test_scenario_stats_accounting(tmp_path) -> None:
    """Pins _scenario_stats' per-group counting, self-normalized fraction,
    and the downtime decomposition (partial_step + restart + ft_resume ==
    downtime; multi-restart trials refuse to decompose)."""
    import json as _json
    import sys

    sys.path.insert(0, REPO)
    from bench import _scenario_stats

    def write(path, events):
        with open(path, "w") as f:
            for ev in events:
                f.write(_json.dumps(ev) + "\n")

    # Group 0 commits at 1..40; group 1 commits at 1..10 (id A), killed at
    # 10.5, new incarnation B's first event (quorum) at 17.5, first commit
    # at 18, then 18..40.
    events = []
    for t in range(1, 41):
        events.append({"ts": float(t), "replica_id": "0:a", "event": "commit", "committed": True})
    for t in range(1, 11):
        events.append({"ts": float(t), "replica_id": "1:A", "event": "commit", "committed": True})
    events.append({"ts": 17.5, "replica_id": "1:B", "event": "quorum"})
    events.append({"ts": 17.9, "replica_id": "1:B", "event": "heal_fetched", "heal_ms": 150.0})
    for t in range(18, 41):
        events.append({"ts": float(t), "replica_id": "1:B", "event": "commit", "committed": True})
    path = tmp_path / "metrics.jsonl"
    write(path, events)

    stats = _scenario_stats(str(tmp_path), str(path), [(10.5, "1")])
    assert stats["per_group"] == {"0": 40, "1": 33}
    assert stats["heals"] == 1
    # downtime 18-10=8; decomposition: partial 0.5 + restart 7.0 + resume 0.5
    assert abs(stats["victim_downtime_s"] - 8.0) < 1e-6
    assert abs(stats["victim_partial_step_s"] - 0.5) < 1e-6
    assert abs(stats["victim_restart_s"] - 7.0) < 1e-6
    assert abs(stats["victim_ft_resume_s"] - 0.5) < 1e-6
    assert abs(
        stats["victim_partial_step_s"]
        + stats["victim_restart_s"]
        + stats["victim_ft_resume_s"]
        - stats["victim_downtime_s"]
    ) < 1e-6
    # Self-normalized fraction: pre-kill rate 10 commits / 9.5 s from t0=1,
    # expected = rate * (40 - 1), actual 33.
    rate = 10 / 9.5
    assert abs(stats["goodput_self_fraction"] - 33 / (rate * 39)) < 1e-6
    # PRIMARY dead-window fraction: the victim's only kill-containing gap is
    # (10, 18) = 8 s, charged minus one median step (1 s) over span 39 s.
    assert stats["victims_recovered"] is True
    assert abs(stats["dead_time_s"] - 7.0) < 1e-6
    assert abs(stats["goodput_deadwindow_fraction"] - (1 - 7.0 / 39.0)) < 1e-3

    # Multi-restart: incarnation B dies too (one event, no commit), C heals.
    events2 = [ev for ev in events if ev["replica_id"] != "1:B"]
    events2.append({"ts": 14.0, "replica_id": "1:B", "event": "quorum"})
    events2.append({"ts": 24.0, "replica_id": "1:C", "event": "quorum"})
    for t in range(25, 41):
        events2.append({"ts": float(t), "replica_id": "1:C", "event": "commit", "committed": True})
    path2 = tmp_path / "metrics2.jsonl"
    write(path2, events2)
    stats2 = _scenario_stats(str(tmp_path), str(path2), [(10.5, "1")])
    assert stats2["victim_downtime_s"] is not None
    assert stats2["victim_restart_s"] is None  # refuses to decompose
    assert stats2["victim_ft_resume_s"] is None


def test_scenario_stats_drain_accounting(tmp_path) -> None:
    """Drain trials use incarnation-aware accounting: the donor keeps
    committing AFTER the notice (that is the point of a drain), so the
    handoff cost is the donor-to-replacement commit gap — which may be
    negative when the pre-warmed replacement overlapped the donor's tail —
    and survivor commit failures after the notice are surfaced."""
    import json as _json
    import sys

    sys.path.insert(0, REPO)
    from bench import _scenario_stats

    def write(path, events):
        with open(path, "w") as f:
            for ev in events:
                f.write(_json.dumps(ev) + "\n")

    # Survivor commits 1..40.  Donor (1:A) receives the notice at 10.5 but
    # COMMITS THROUGH 13 (finishing its in-flight steps); replacement 1:B
    # first commits at 15, i.e. a 2 s handoff gap charged minus the 1 s
    # median step.  One survivor failed commit BEFORE the notice must not
    # count against the drain.
    events = [
        {"ts": 5.5, "replica_id": "0:a", "event": "commit", "committed": False},
    ]
    for t in range(1, 41):
        events.append({"ts": float(t), "replica_id": "0:a", "event": "commit", "committed": True})
    for t in range(1, 14):
        events.append({"ts": float(t), "replica_id": "1:A", "event": "commit", "committed": True})
    for t in range(15, 41):
        events.append({"ts": float(t), "replica_id": "1:B", "event": "commit", "committed": True})
    path = tmp_path / "metrics.jsonl"
    write(path, events)

    plan = {"type": "drain", "victim": 1}
    stats = _scenario_stats(str(tmp_path), str(path), [(10.5, "1")], plan)
    assert abs(stats["drain_handoff_gap_s"] - 2.0) < 1e-6
    assert abs(stats["dead_time_s"] - 1.0) < 1e-6  # gap minus median step
    assert abs(stats["victim_downtime_s"] - 2.0) < 1e-6
    assert stats["victims_recovered"] is True
    # Pre-notice failure excluded from the post-notice count.
    assert stats["failed_commits_after_kill"] == {"0": 0}
    assert abs(stats["goodput_deadwindow_fraction"] - (1 - 1.0 / 39.0)) < 1e-3

    # Overlapped handoff: replacement's first commit BEFORE the donor's
    # last -> negative gap, zero dead time, downtime clamped to 0.
    events2 = []
    for t in range(1, 41):
        events2.append({"ts": float(t), "replica_id": "0:a", "event": "commit", "committed": True})
    for t in range(1, 14):
        events2.append({"ts": float(t), "replica_id": "1:A", "event": "commit", "committed": True})
    for t in range(12, 41):
        events2.append({"ts": t + 0.5, "replica_id": "1:B", "event": "commit", "committed": True})
    path2 = tmp_path / "metrics2.jsonl"
    write(path2, events2)
    stats2 = _scenario_stats(str(tmp_path), str(path2), [(10.5, "1")], plan)
    assert stats2["drain_handoff_gap_s"] == -0.5
    assert stats2["dead_time_s"] == 0.0
    assert stats2["victim_downtime_s"] == 0.0
    assert stats2["goodput_deadwindow_fraction"] == 1.0


def test_bench_headline_equals_obs_report(tmp_path) -> None:
    """The benchmark's dead-window goodput and `python -m
    torchft_tpu.obs.report` must agree EXACTLY on the same recorded stream
    — they now share one implementation (obs/report.py::deadwindow), and
    the fault schedule rides in the stream as `fault` records, so the
    report needs nothing but the JSONL."""
    import json as _json
    import sys

    sys.path.insert(0, REPO)
    from bench import _scenario_stats
    from torchft_tpu.obs import report

    kill_ts = 10.5
    events = []
    for t in range(1, 41):
        events.append({"ts": float(t), "replica_id": "0:a", "event": "commit", "committed": True})
    for t in range(1, 11):
        events.append({"ts": float(t), "replica_id": "1:A", "event": "commit", "committed": True})
    for t in range(18, 41):
        events.append({"ts": float(t), "replica_id": "1:B", "event": "commit", "committed": True})
    # The record bench's fault logger writes at kill time (explicit ts).
    events.append(
        {"ts": kill_ts, "replica_id": "bench-driver", "event": "fault",
         "kind": "kill", "group": "1", "plan": "single"}
    )
    path = tmp_path / "metrics.jsonl"
    with open(path, "w") as f:
        for ev in events:
            f.write(_json.dumps(ev) + "\n")

    bench_stats = _scenario_stats(str(tmp_path), str(path), [(kill_ts, "1")])
    report_result = report.attribute(report.read_events([str(path)]))
    assert bench_stats["goodput_deadwindow_fraction"] is not None
    assert report_result["goodput"]["deadwindow_fraction"] == pytest.approx(
        bench_stats["goodput_deadwindow_fraction"], abs=5e-5
    )
    assert report_result["goodput"]["dead_time_s"] == pytest.approx(
        bench_stats["dead_time_s"], abs=5e-3
    )
    assert report_result["goodput"]["victims_recovered"] is True
    # The report also yields a per-step table over the same stream.
    assert report_result["steps"], "attribution table empty"


def test_scenario_stats_double_kill_and_unrecovered(tmp_path) -> None:
    """Dead-window accounting under churn: two kills of the same victim
    charge two gaps; a victim that never recommits invalidates the trial
    (victims_recovered False, no fraction)."""
    import json as _json
    import sys

    sys.path.insert(0, REPO)
    from bench import _scenario_stats

    def write(path, events):
        with open(path, "w") as f:
            for ev in events:
                f.write(_json.dumps(ev) + "\n")

    events = []
    for t in range(1, 41):
        events.append({"ts": float(t), "replica_id": "0:a", "event": "commit", "committed": True})
    # Victim commits 1..10 (A), killed at 10.5; B commits 18..22, killed at
    # 22.5; C commits 30..40.  Gaps charged: (10,18)=8 and (22,30)=8, each
    # minus the 1 s median step -> dead 14 over span 39.
    for t in range(1, 11):
        events.append({"ts": float(t), "replica_id": "1:A", "event": "commit", "committed": True})
    for t in range(18, 23):
        events.append({"ts": float(t), "replica_id": "1:B", "event": "commit", "committed": True})
    for t in range(30, 41):
        events.append({"ts": float(t), "replica_id": "1:C", "event": "commit", "committed": True})
    path = tmp_path / "metrics.jsonl"
    write(path, events)

    stats = _scenario_stats(str(tmp_path), str(path), [(10.5, "1"), (22.5, "1")])
    assert stats["kills"] == 2
    assert stats["victims_recovered"] is True
    assert abs(stats["dead_time_s"] - 14.0) < 1e-6
    assert abs(stats["goodput_deadwindow_fraction"] - (1 - 14.0 / 39.0)) < 1e-3
    # Two-kill trials don't pretend to decompose a single dead window.
    assert stats["victim_restart_s"] is None

    # Unrecovered victim: killed at 10.5, never commits again.
    events3 = [
        ev
        for ev in events
        if not str(ev["replica_id"]).startswith("1:") or ev["ts"] <= 10.0
    ]
    path3 = tmp_path / "metrics3.jsonl"
    write(path3, events3)
    stats3 = _scenario_stats(str(tmp_path), str(path3), [(10.5, "1")])
    assert stats3["victims_recovered"] is False
    assert stats3["goodput_deadwindow_fraction"] is None
