"""Integration smokes: each test drives one live cell of a subsystem --
real lighthouse, real Managers, real sockets, replica groups as threads or
JAX-free subprocesses on this host -- and asserts what the cell COUNTED:
bytes per lane, commits, failed commits, bitwise parity, records on both
sides of a fault, alerts within N rounds.  No assertion compares two times
or two rates measured here: nothing timed on a loaded CPU host is a speed,
and a red run of this file means a defect.  The cells live beside this file
(``ring_cells``, ``fleet_cells``, ``heal_cells``, ``elastic_cells``,
``failover_cells``, ``diloco_cells``).
"""

import os
import subprocess
import sys

import pytest

import diloco_cells
import elastic_cells
import failover_cells
import fleet_cells
import heal_cells
import ring_cells

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_transfer_quick_smoke() -> None:
    """The striped multi-donor fetch and the failover from a dead first
    donor on a small dict: every fetch returns the whole state, bitwise
    (asserted inside the cell)."""
    payload = heal_cells.run_quick(gb=0.008, buffers=8)
    results = {(r["donors"], r["donor_killed"]): r for r in payload["results"]}
    assert set(results) == {(1, False), (2, False), (2, True)}
    for r in results.values():
        assert r["fetched_bytes"] == payload["state_dict_bytes"]


def test_ha_quick_smoke() -> None:
    """2 HA lighthouse replicas, 2 replica groups, one SIGKILL of the
    active leader mid-run.  The whole failover arc: a standby takes the
    lease at epoch+1, every group commits again after the kill, ZERO failed
    commits on the healthy groups, /metrics + straggler-sentinel continuity
    on the new leader, the surviving standby (none in this shape) never
    dual-serves, and the takeover lands in the obs stream."""
    payload = failover_cells.run_quick()
    assert payload["leader_epoch_after"] == payload["leader_epoch_before"] + 1
    assert payload["resumed_after_kill"], payload
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
    """A 4-rank topology-parity check (ring2d active, results within
    tolerance of the flat ring, replica-consistent bitwise, int payloads
    uncompressed) plus a 4-group control cell under a pinned ring2d
    topology with a 2-victim correlated preemption wave: the surviving half
    reforms a quorum and keeps committing (the post-wave 2-group world
    crosses the auto crossover back to the flat ring), the lighthouse
    flight-recorder dump reconstructs the wave's quorum transitions, and
    the cell leaks zero fds."""
    payload = fleet_cells.run_quick()
    parity = payload["parity"]
    for key in ("ring2d_active", "int_bypass_ok", "replica_consistent",
                "topologies_close", "ok"):
        assert parity[key] is True, (key, parity)
    (cell,) = payload["cells"]
    assert cell["warmed_groups"] == cell["groups"] == 4
    assert cell["quorum_reformed"], cell
    assert cell["wave_reconstructed"], cell
    assert cell["flight_dump_found"]
    # Zero leaked sockets/fds across the whole cell (driver-side).
    assert cell["fd_leaked"] == 0, cell
    assert payload["fd_leaked_total"] == 0
    # The lighthouse's histograms carried real observations.
    assert cell["quorum_formation"]["count"] > 0
    assert cell["heartbeat_fanin"]["count"] > 0
    assert cell["rpc"]["Quorum"]["count"] > 0
    assert payload["ok"], payload


def test_allreduce_quick_smoke() -> None:
    """The striped multi-lane ring (1 vs 2 lanes) and the
    pipelined-vs-monolithic bucket paths complete and commit on a small
    dict."""
    payload = ring_cells.run_quick()
    assert {r["lanes"] for r in payload["lanes"]} == {1, 2}
    for r in payload["lanes"]:
        assert len(r["lane_bytes_sent"]) == r["lanes"]
        assert all(b > 0 for b in r["lane_bytes_sent"])
    modes = {r["mode"]: r for r in payload["e2e"]}
    assert set(modes) == {"pipelined", "monolithic"}
    for r in modes.values():
        assert r["committed"] == r["steps"]  # healthy run: every step lands
    # Same gradients either way: the two paths hand the ring the same bytes.
    assert modes["pipelined"]["wire_bytes"] == modes["monolithic"]["wire_bytes"]


def test_ring_engine_quick_smoke() -> None:
    """The ring engines side by side: py + native at the same
    unshaped-loopback configuration move the same bytes per lane, and the
    live bitwise parity pin holds."""
    payload = ring_cells.run_engine_quick(payload_mb=4.0, lanes=2)
    by_engine = {c["engine"]: c for c in payload["cells"]}
    assert set(by_engine) == {"py", "native"}  # each request resolved to itself
    for cell in by_engine.values():
        assert len(cell["lane_bytes_sent"]) == cell["lanes"]
    # Same config, same wire bytes: the engine is a pure hot-loop swap.
    assert (by_engine["py"]["lane_bytes_sent"]
            == by_engine["native"]["lane_bytes_sent"])
    assert payload["parity_bitwise"] is True


def test_transport_quick_smoke() -> None:
    """The same-host lane transports side by side: shm and tcp move the same
    frames, bitwise transport parity holds, and a striped allreduce crosses
    into the native engine once per op (call count asserted)."""
    payload = ring_cells.run_transport_quick(payload_mb=4.0, lanes=2)
    by_transport = {c["transport"]: c for c in payload["cells"]}
    assert set(by_transport) == {"tcp", "shm"}  # shm armed, not degraded
    # Same frames either way: the transport is a pure data-plane swap.
    assert (by_transport["tcp"]["lane_bytes_sent"]
            == by_transport["shm"]["lane_bytes_sent"])
    assert payload["parity_bitwise"] is True
    ms = payload["multi_stripe"]
    assert ms is not None, "native ring engine did not resolve"
    assert ms["stripes_per_op"] > 1
    assert ms["pass_calls"] == ms["ops"], ms
    assert ms["one_call_per_op"] is True


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
    """Erasure-coded healing at a small state size: the donor-side encode
    runs on the transport's snapshotter and never on the train thread, the
    reconstruction is BITWISE-equal to the donor stream, the
    SIGKILLed-donor-set wave reconstructs from surviving shard holders, and
    the manager-level prefer-mode wave heals with the survivors
    committing."""
    payload = heal_cells.run_ec_quick(gb=0.008, buffers=8)
    cells = {c["op"]: c for c in payload["ec"]}
    assert set(cells) == {"ec_encode", "ec_reconstruct", "ec_wave",
                          "ec_manager_wave"}
    # Donor-side: the train thread pays for no encode.  Every enqueue
    # returned (the loop reached its end), the queue drained, at least one
    # generation was encoded, and every encode ran on the snapshotter.
    enc = cells["ec_encode"]
    assert enc["drained"] is True
    assert enc["encode_calls"] >= 1 and enc["latest_encoded_step"] >= 1, enc
    assert enc["shards_held"] >= 1, enc
    assert enc["encode_threads"] == ["tpuft_http_snapshot"], enc
    assert enc["train_thread"] not in enc["encode_threads"]
    assert cells["ec_reconstruct"]["bitwise"] is True
    assert cells["ec_reconstruct"]["shards_used"]
    wave = cells["ec_wave"]
    assert wave["ok"] and wave["donor_fetch_failed"] and wave["bitwise"]
    assert wave["donors_sigkilled"] >= 2
    mwave = cells["ec_manager_wave"]
    assert mwave["ok"], mwave
    # The heal path never touches survivors in prefer mode; the SIGKILL
    # itself racing mid-allreduce may fail ONE survivor round (the same
    # one-failed-round cost every crash pays).
    assert mwave["survivor_failed_commits"] <= 1
    assert mwave["ec_reconstructions"] >= 1
    assert mwave["victim_post_heal_commits"] > 0


def test_link_quick_smoke() -> None:
    """Slow-link sentinel: with ONE peer's outbound link re-shaped 10x
    slower mid-run (no reconfigure -- invisible to heartbeat timeouts and to
    the straggler sentinel's wall-minus-waits signal), the lighthouse raises
    a slow_link alert within a bounded number of victim commit rounds,
    names the victim as the reporting sender, the healthy control run
    raises ZERO link alerts, and the attribution split's fractions sum to
    ~1 with the ADDED wall landing on the wire/shaping/stall side."""
    r = ring_cells.run_link()
    assert r["ok"], r
    assert r["detected"] is True
    assert r["detection_rounds"] is not None and r["detection_rounds"] <= 10
    assert r["alert_src_is_victim"] is True
    assert r["healthy"]["link_alerts"] == 0
    assert r["degraded"]["link_alerts"] >= 1
    # Every group of both cells committed every round: a degraded link is
    # slow, not broken -- no failed commits, which is exactly why only the
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


def test_peer_kill_hop_timeline_brackets_fault() -> None:
    """Mid-allreduce peer kill: the survivors latch the error, fail the
    commit cleanly and rebuild every lane (all inside ``ok``), and the
    surviving group's hop timeline BRACKETS the kill -- pre-fault hops
    banked when abort() tore the generation down, plus hops from the
    rebuilt lanes.  A timeline that only covers one side of the fault
    window is useless as a black box.  The kill is placed by a count (the
    victim's own bytes on the wire in that step), so one run decides."""
    r = ring_cells.bench_peer_kill(lanes=2)
    assert r["ok"], r
    assert r["hop_timeline_records"] > 0
    assert r["hop_timeline_brackets_fault"] is True
    assert r["kill_ts"] is not None


def test_device_prep_quick_smoke() -> None:
    """Device-resident wire prep end to end: a small 2-group run with the
    on-device bf16 cast (and the sharded fetch, which engages under the
    suite's forced multi-device platform) commits every step like the
    host-cast reference and halves the D2H fetch bytes."""
    trials = {
        mode: ring_cells.bench_e2e(
            lanes=2, pipelined=True, steps=2, grads_mb=1.0, n_leaves=4,
            bucket_mb=0.5, timeout_s=60.0, device_prep=prep, sharded=shard,
            wire_dtype="bf16",
        )
        for mode, (prep, shard) in {
            "host": (False, False),
            "prep": (True, False),
            "sharded": (True, True),
        }.items()
    }
    for name, r in trials.items():
        assert r["committed"] == r["steps"], name
        assert r["d2h_bytes"] > 0 and r["wire_bytes"] > 0
    # The headline: device-side bf16 cast halves the fetch bytes.
    ratio = trials["host"]["d2h_bytes"] / trials["prep"]["d2h_bytes"]
    assert 1.9 <= ratio <= 2.1, ratio
    import jax

    if len(jax.local_devices()) > 1:
        assert trials["sharded"]["fetch_slices"] > 0


def test_diloco_quick_smoke() -> None:
    """The streaming semi-sync plane: 2 replica groups, small model, shaped
    60 ms-RTT link.  The blocking port and the streaming engine both commit
    every round with the state fragmented and on the wire, the int8+EF wire
    costs <= 0.27x the f32 wire, and error feedback bounds the drift plain
    int8 accumulates."""
    payload = diloco_cells.run_quick()
    overlap = payload["overlap"]
    cells = overlap["cells"]
    assert set(cells) == {"blocking", "streaming"}
    for name in ("blocking", "streaming"):
        # Healthy run: every counted round committed, and the state
        # actually fragmented + rode the wire.
        assert cells[name]["committed_rounds"] == overlap["rounds"], cells[name]
        assert cells[name]["fragments"] >= 2
        assert cells[name]["wire_bytes"] > 0
    # Streaming changes WHEN a fragment is synced, never what is sent.
    assert cells["streaming"]["fragment_rounds"] == cells["blocking"]["fragment_rounds"]
    assert cells["streaming"]["wire_bytes"] == cells["blocking"]["wire_bytes"]
    quant = payload["quant"]
    assert set(quant["drift_vs_f32"]) == {"bf16", "int8", "int8_noef"}
    assert quant["ef_bounds_drift"], quant
    assert quant["wire_ratio_int8"] <= 0.27, quant
    # The 4-bit cell rides in its own keys: packed wire <= 0.14x f32, EF
    # bounds the no-EF drift, and the EF drift sits at the 127/7 step-ratio
    # floor relative to int8 (no accumulation blowup).
    assert set(quant["int4_drift_vs_f32"]) == {"int4", "int4_noef"}
    assert quant["int4_ef_bounds_drift"], quant
    assert quant["int4_drift_at_step_ratio_floor"], quant
    assert quant["wire_ratio_int4"] <= 0.14, quant


def test_elastic_quick_smoke() -> None:
    """A 3-group spot-market trace (leave/join/leave over cooperative drain
    notices): ZERO failed survivor commits across every transition,
    constant global batch in every committed step record, incremental lane
    reconfiguration engaged, proactive EC re-shard on membership change,
    and no leaked fds."""
    payload = elastic_cells.run_quick()
    cell = payload["elastic"]
    # The headline criteria: departures are notice-driven, so NO survivor
    # ever fails a commit, and the batch engine holds the global batch
    # constant through every membership size it saw.
    assert cell["survivor_failed_commits"] == 0, cell
    assert cell["elastic_records"]["constant_global_batch"] is True, cell
    assert cell["fd_leaked"] == 0
    assert cell["transitions_stabilized"] == len(cell["trace"]) == 3, cell
    assert cell["committed_steps"] > 0
    assert cell["membership_changes"] > 0
    assert cell["reconfigure_modes"].get("incremental", 0) > 0, cell
    assert cell["ec_reshard_pushes"] > 0, cell
    assert cell["elastic_records"]["committed_with_plan"] > 0
    assert len(cell["elastic_records"]["participants_seen"]) >= 2
    assert payload["ok"], payload


def test_example_emits_committed_line(tmp_path) -> None:
    """Runs the example app for a couple of steps in a subprocess (tiny
    model, CPU platform, 1 replica group) and asserts the exact log line the
    whole system is read by (`committed=True`) appears."""
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
