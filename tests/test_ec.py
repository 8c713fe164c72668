"""Erasure-coded peer state (torchft_tpu/ec): donor-free healing tests.

Covers the codec contract (ANY k of k+m shards decode bitwise-identically,
corrupt shards are detected by checksum and excluded), the integrity-checked
HTTP plumbing (shard endpoints, per-buffer CRCs on the striped donor fetch),
the ECPlane write path (encode on the background snapshotter, placement,
parity push), and the Manager's recovery-planner fallback — including the
repeated-donor-death arc: >= 3 consecutive failed quorums riding the
``_apply_pending_state_dict`` latch path before a successful reconstruction.
"""

import itertools
import json
from typing import Any, Dict, List
from unittest.mock import MagicMock

import numpy as np
import pytest

from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.checkpointing.serialization import (
    flatten_state_dict,
    state_dict_frames,
    unflatten_state_dict,
)
from torchft_tpu.ec import gf
from torchft_tpu.ec.encoder import (
    decode_shards,
    decode_stream,
    encode_stream,
    read_shard,
    write_shard,
)
from torchft_tpu.ec.placement import shard_holder, shards_for_holder
from torchft_tpu.ec.store import (
    ECConfig,
    ECPlane,
    ShardStore,
    fetch_inventory,
    fetch_shard,
    push_shard,
    reconstruct,
)

from test_manager import FakeCollective, make_manager, make_quorum, store  # noqa: F401


def _state(n: int = 8, per: int = 500) -> Dict[str, np.ndarray]:
    return {f"layer_{i}": np.full((per,), float(i) + 0.25, np.float32) for i in range(n)}


# ---------------------------------------------------------------------------
# Codec property tests
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,m", [(2, 1), (3, 2), (5, 3)])
def test_decode_every_k_subset_is_bitwise_identical(k: int, m: int) -> None:
    """The MDS contract: EVERY k-subset of the k+m shards reproduces the
    canonical stream byte-for-byte — which is what makes an EC heal
    bitwise-equal to a donor fetch."""
    state = {
        "a": np.arange(997, dtype=np.float32),  # odd sizes force padding +
        "b": np.full((13, 7), -1.5, np.float64),  # shard-boundary crossings
        "count": np.int64(41),
    }
    meta, bufs = flatten_state_dict(state, step=9)
    prefix, total = state_dict_frames(meta, bufs)
    orig = bytes(prefix) + b"".join(b.tobytes() for b in bufs)
    shards = encode_stream(meta, bufs, k, m, step=9)
    assert len(shards) == k + m
    for subset in itertools.combinations(range(k + m), k):
        raw = decode_shards(
            {i: shards[i].payload for i in subset}, k, m, shards[0].total_len
        )
        assert raw == orig, f"subset {subset} decoded differently"
        meta2, bufs2 = decode_stream([shards[i] for i in subset])
        assert all(x.tobytes() == y.tobytes() for x, y in zip(bufs, bufs2))


def test_decode_needs_k_shards() -> None:
    meta, bufs = flatten_state_dict(_state(2), step=0)
    shards = encode_stream(meta, bufs, 3, 2, step=0)
    with pytest.raises(ValueError, match="need 3 shards"):
        decode_shards({0: shards[0].payload, 4: shards[4].payload}, 3, 2,
                      shards[0].total_len)


def test_shard_wire_roundtrip_and_corruption_detected() -> None:
    meta, bufs = flatten_state_dict(_state(3), step=2)
    shard = encode_stream(meta, bufs, 2, 2, step=2)[3]
    frame = write_shard(shard)
    back = read_shard(frame)
    assert back.idx == 3 and back.payload.tobytes() == shard.payload.tobytes()
    torn = bytearray(frame)
    torn[-1] ^= 0xFF
    with pytest.raises(IOError, match="checksum mismatch"):
        read_shard(bytes(torn))


def test_gf_cauchy_submatrices_invert() -> None:
    """Spot-check the MDS property at the matrix level: random k x k row
    subsets of [I; Cauchy] invert cleanly."""
    k, m = 4, 3
    gen = np.vstack([np.eye(k, dtype=np.uint8), gf.cauchy_matrix(m, k)])
    rng = np.random.default_rng(7)
    for _ in range(20):
        rows = sorted(rng.choice(k + m, size=k, replace=False))
        sub = gen[rows]
        inv = gf.gf_mat_inv(sub)
        prod = np.zeros((k, k), dtype=np.uint8)
        for i in range(k):
            for j in range(k):
                v = 0
                for t in range(k):
                    v ^= gf.gf_mul(int(sub[i, t]), int(inv[t, j]))
                prod[i, j] = v
        assert (prod == np.eye(k, dtype=np.uint8)).all(), rows


# ---------------------------------------------------------------------------
# Placement + store
# ---------------------------------------------------------------------------


def test_placement_covers_all_shards_and_rotates() -> None:
    holders = [0, 1, 2, 3]
    n = 6
    for step in (0, 1, 17):
        owned = [shards_for_holder(step, h, holders, n) for h in holders]
        assert sorted(idx for o in owned for idx in o) == list(range(n))
        for h, o in zip(holders, owned):
            assert all(shard_holder(step, i, holders) == h for i in o)
    # Rotation: the same shard lands on different holders across steps.
    assert shard_holder(0, 0, holders) != shard_holder(1, 0, holders)


def test_shard_store_retention_and_coverage() -> None:
    st = ShardStore(retain=2)
    meta, bufs = flatten_state_dict(_state(2), step=0)
    for step in (1, 2, 3):
        for s in encode_stream(meta, bufs, 2, 1, step=step):
            st.put(s)
    assert st.have(1) == []  # pruned (retain=2)
    assert st.have(2) == [0, 1, 2] and st.have(3) == [0, 1, 2]
    assert st.coverage() == (3, 3)
    inv = st.inventory(3)
    assert inv["k"] == 2 and inv["m"] == 1 and inv["shards"] == [0, 1, 2]
    assert st.inventory(99)["shards"] == []


# ---------------------------------------------------------------------------
# HTTP shard endpoints + striped-fetch integrity
# ---------------------------------------------------------------------------


def test_shard_endpoints_roundtrip_and_bad_post() -> None:
    store_ = ShardStore(retain=2)
    holder = HTTPTransport(timeout=10.0)
    holder.attach_shard_store(store_)
    try:
        meta, bufs = flatten_state_dict(_state(4), step=5)
        shards = encode_stream(meta, bufs, 3, 1, step=5)
        store_.put(shards[0])
        push_shard(holder.metadata(), shards[3], 5.0)  # POST path
        inv = fetch_inventory(holder.metadata(), 5, 5.0)
        assert inv["shards"] == [0, 3]
        got = fetch_shard(holder.metadata(), 5, 3, 5.0)
        assert got.payload.tobytes() == shards[3].payload.tobytes()
        # Torn push: refused with 400, never stored.
        frame = bytearray(write_shard(shards[1]))
        frame[-1] ^= 0xFF
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            f"{holder.metadata()}/ec/shard/5/1", data=bytes(frame), method="POST"
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=5.0)
        assert exc.value.code == 400
        assert store_.have(5) == [0, 3]
        # Missing shard and malformed indices: 4xx, never a 500.
        for path in ("/ec/shard/5/7", "/ec/shard/x/1", "/ec/nope/5"):
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"{holder.metadata()}{path}", timeout=5.0)
            assert exc.value.code in (400, 404)
    finally:
        holder.shutdown()


def test_reconstruct_excludes_corrupt_shard_and_uses_parity() -> None:
    store_ = ShardStore(retain=2)
    holder = HTTPTransport(timeout=10.0)
    holder.attach_shard_store(store_)
    try:
        meta, bufs = flatten_state_dict(_state(5), step=4)
        shards = encode_stream(meta, bufs, 3, 2, step=4)
        for s in shards:
            store_.put(s)
        # Corrupt one stored DATA shard in place (its recorded CRC is stale).
        store_.get(4, 1).payload.setflags(write=True)
        store_.get(4, 1).payload[10] ^= 0xFF
        meta2, bufs2, stats = reconstruct([holder.metadata()], 4, timeout=10.0)
        assert all(x.tobytes() == y.tobytes() for x, y in zip(bufs, bufs2))
        assert stats["corrupt"] == 1 and stats["parity_used"] >= 1
        assert 1 not in stats["shards_used"]
    finally:
        holder.shutdown()


def test_reconstruct_times_out_below_k() -> None:
    store_ = ShardStore(retain=2)
    holder = HTTPTransport(timeout=10.0)
    holder.attach_shard_store(store_)
    try:
        meta, bufs = flatten_state_dict(_state(2), step=3)
        shards = encode_stream(meta, bufs, 3, 1, step=3)
        store_.put(shards[0])
        store_.put(shards[1])  # only 2 of k=3 reachable
        with pytest.raises(RuntimeError, match="timed out"):
            reconstruct([holder.metadata()], 3, timeout=1.0, poll_s=0.1)
    finally:
        holder.shutdown()


def test_striped_fetch_crc_detects_corruption_and_fails_over() -> None:
    """Satellite: a torn/corrupt donor stream mid-heal fails the stripe
    (failover to the next donor); with EVERY donor corrupt the fetch
    raises — the error latches upstream instead of installing garbage."""
    mk = lambda: _state(6)
    good = HTTPTransport(timeout=10.0)
    bad = HTTPTransport(timeout=10.0)
    dst = HTTPTransport(timeout=10.0)
    try:
        for t in (good, bad):
            t.send_checkpoint([1], step=0, state_dict=mk(), timeout=10.0)
            assert t.wait_snapshot(10.0)
        # Corrupt the bad donor's served copy AFTER its CRCs were computed.
        bad._state[1][2][7] += 1.0
        out = dst.recv_checkpoint(1, [bad.metadata(), good.metadata()], step=0,
                                  timeout=10.0)
        ref = mk()
        assert all(np.array_equal(out[key], ref[key]) for key in ref)
        good._state[1][2][7] += 1.0  # now both donors corrupt
        with pytest.raises(RuntimeError, match="failed on all"):
            dst.recv_checkpoint(1, [bad.metadata(), good.metadata()], step=0,
                                timeout=10.0)
    finally:
        for t in (good, bad, dst):
            t.shutdown()


def test_full_fetch_crc_detects_corruption() -> None:
    """The single-donor /full path verifies too (read_state_dict)."""
    src = HTTPTransport(timeout=10.0)
    dst = HTTPTransport(timeout=10.0)
    try:
        src.send_checkpoint([1], step=0, state_dict=_state(3), timeout=10.0)
        assert src.wait_snapshot(10.0)
        src._state[1][0][0] += 1.0
        with pytest.raises(Exception, match="checksum mismatch"):
            dst.recv_checkpoint(1, src.metadata(), step=0, timeout=10.0)
    finally:
        src.shutdown()
        dst.shutdown()


# ---------------------------------------------------------------------------
# ECPlane write path
# ---------------------------------------------------------------------------


def test_ec_plane_encodes_on_snapshot_and_pushes_parity() -> None:
    """Two groups' planes riding real transports: each materializes its
    placement-assigned shards from its own snapshot, and the step's
    designated pusher delivers parity to the peer that owns it."""
    cfg = ECConfig(k=2, m=2)
    t0, t1 = HTTPTransport(timeout=10.0), HTTPTransport(timeout=10.0)
    planes = [ECPlane(cfg) for _ in range(2)]
    try:
        addrs = {0: t0.metadata(), 1: t1.metadata()}
        for rank, (t, p) in enumerate(zip((t0, t1), planes)):
            t.attach_shard_store(p.store)
            t.set_snapshot_hook(p.on_snapshot)
            p.set_peers([0, 1], [addrs[0], addrs[1]], rank)
        state = _state(4)
        step = 3
        for t in (t0, t1):
            t.enqueue_snapshot(step, state, serve=False)
        assert t0.wait_snapshot(10.0) and t1.wait_snapshot(10.0)
        n = cfg.n_shards
        own0 = shards_for_holder(step, 0, [0, 1], n)
        own1 = shards_for_holder(step, 1, [0, 1], n)
        # Every locally-assigned shard is materialized...
        assert set(planes[0].store.have(step)) >= set(own0)
        assert set(planes[1].store.have(step)) >= set(own1)
        # ...full coverage across the pair, and reconstruction works from
        # the two stores over HTTP.
        meta, bufs = flatten_state_dict(state, step=step)
        m2, b2, stats = reconstruct([addrs[0], addrs[1]], step, timeout=10.0)
        assert all(x.tobytes() == y.tobytes() for x, y in zip(bufs, b2))
        out = unflatten_state_dict(m2, b2)
        assert all(np.array_equal(np.asarray(out[k]), state[k]) for k in state)
    finally:
        t0.shutdown()
        t1.shutdown()


def test_ec_config_env_and_validation(monkeypatch) -> None:
    monkeypatch.setenv("TPUFT_EC_K", "4")
    monkeypatch.setenv("TPUFT_EC_M", "3")
    monkeypatch.setenv("TPUFT_EC_MODE", "prefer")
    cfg = ECConfig.from_env()
    assert (cfg.k, cfg.m, cfg.mode) == (4, 3, "prefer")
    assert cfg.enabled and cfg.n_shards == 7
    monkeypatch.setenv("TPUFT_EC_MODE", "sometimes")
    with pytest.raises(ValueError, match="TPUFT_EC_MODE"):
        ECConfig.from_env()
    monkeypatch.delenv("TPUFT_EC_MODE")
    monkeypatch.setenv("TPUFT_EC_K", "0")
    assert not ECConfig.from_env().enabled


# ---------------------------------------------------------------------------
# Manager recovery-planner fallback (fake wire)
# ---------------------------------------------------------------------------


def _donor_state(step: int) -> Dict[str, Any]:
    """The shape _manager_state_dict serves: user trees + bookkeeping."""
    return {
        "user": {"default": {"w": np.full((64,), 2.5, np.float32),
                             "b": np.arange(8, dtype=np.float32)}},
        "tpuft": {"step": step, "batches_committed": step * 2},
    }


def _heal_quorum(max_step: int, participants: List[str]):
    q = make_quorum(
        quorum_id=2,
        replica_rank=2,
        replica_world_size=3,
        max_step=max_step,
        max_replica_rank=None,
        max_world_size=2,
        heal=True,
        recover_src=0,
        donor_ranks=[0, 1],
        donor_addrs=["dead-donor-a:1", "dead-donor-b:1"],
    )
    q.participant_replica_ranks = list(range(len(participants)))
    q.participant_manager_addresses = participants
    return q


def test_repeated_donor_death_latches_then_ec_reconstructs(
    store, tmp_path, monkeypatch  # noqa: F811
) -> None:
    """The satellite arc: >= 3 consecutive quorums whose donor fetch dies
    drive the `_apply_pending_state_dict` latch path (failed vote, no
    crash, retry), each retry paced by the decorrelated heal backoff; the
    4th quorum finds shard holders reachable and the EC reconstruction
    heals — bitwise-equal to what the donors would have served."""
    from torchft_tpu.metrics import METRICS_PATH_ENV

    events_path = tmp_path / "ec.jsonl"
    monkeypatch.setenv(METRICS_PATH_ENV, str(events_path))
    monkeypatch.setenv("TPUFT_EC_K", "2")
    monkeypatch.setenv("TPUFT_EC_M", "1")
    monkeypatch.setenv("TPUFT_HEAL_BACKOFF_BASE_S", "0.01")
    monkeypatch.setenv("TPUFT_HEAL_BACKOFF_CAP_S", "0.05")

    max_step = 5
    donor_tree = _donor_state(max_step)
    meta, bufs = flatten_state_dict(donor_tree, step=max_step)
    shards = encode_stream(meta, bufs, 2, 1, step=max_step)

    holder = HTTPTransport(timeout=10.0)
    holder_store = ShardStore(retain=2)
    holder.attach_shard_store(holder_store)

    applied: Dict[str, Any] = {}
    transport = MagicMock()
    transport.serves_all_donors = True
    transport.metadata.return_value = "http://healer:0"
    transport.recv_checkpoint.side_effect = RuntimeError("donor dead")
    transport.materialize.side_effect = (
        lambda m, b: unflatten_state_dict(m, b)
    )

    client = MagicMock()
    client.should_commit.return_value = False

    try:
        manager, _, _ = make_manager(
            store,
            client_mock=client,
            checkpoint_transport=transport,
            load_state_dict=lambda sd: applied.update(sd),
            state_dict=lambda: applied,
        )
        # The plane resolves peer addresses verbatim in tests (no dial).
        assert manager._ec is not None
        manager._ec._resolve_peer = None

        # Rounds 1-3: donors dead, shard holders EMPTY -> heal fails, the
        # error latches, the vote fails, the worker survives.
        for round_no in range(3):
            client._quorum.return_value = _heal_quorum(
                max_step, ["http://dead-holder:1"]
            )
            manager.start_quorum()
            manager.wait_quorum()
            assert manager.errored() is not None, f"round {round_no}"
            # _apply_pending_state_dict's latch path: healing with nothing
            # fetched fails the commit instead of crashing the worker.
            assert manager.should_commit() is False
            assert manager._heal_failures == round_no + 1
        assert not applied

        # Round 4: the shard holders are reachable and populated -> the
        # SAME quorum round falls back to reconstruction and heals.
        for s in shards:
            holder_store.put(s)
        client._quorum.return_value = _heal_quorum(max_step, [holder.metadata()])
        client.should_commit.return_value = True
        manager.start_quorum()
        manager.wait_quorum()
        assert manager.errored() is None
        assert manager.should_commit() is True
        assert manager._heal_failures == 0
        assert manager.current_step() == max_step + 1  # healed + committed
        np.testing.assert_array_equal(
            np.asarray(applied["w"]), donor_tree["user"]["default"]["w"]
        )
        np.testing.assert_array_equal(
            np.asarray(applied["b"]), donor_tree["user"]["default"]["b"]
        )
    finally:
        manager.shutdown()
        holder.shutdown()

    events = [json.loads(l) for l in events_path.read_text().splitlines()]
    kinds = [e["event"] for e in events]
    assert kinds.count("heal_start") == 4
    recon = [e for e in events if e["event"] == "ec_reconstruct"]
    assert len(recon) == 1 and recon[0]["step"] == max_step
    assert recon[0]["parity_used"] == 0 and recon[0]["holders"] == 1
    spans = {e["phase"] for e in events if e["event"] == "span"}
    assert "ec_reconstruct" in spans


def test_prefer_mode_heals_without_touching_donors(
    store, tmp_path, monkeypatch  # noqa: F811
) -> None:
    """TPUFT_EC_MODE=prefer: the donor fetch is never attempted when the
    shard holders can serve — the fully donor-free heal."""
    monkeypatch.setenv("TPUFT_EC_K", "2")
    monkeypatch.setenv("TPUFT_EC_M", "1")
    monkeypatch.setenv("TPUFT_EC_MODE", "prefer")

    max_step = 7
    donor_tree = _donor_state(max_step)
    meta, bufs = flatten_state_dict(donor_tree, step=max_step)
    holder = HTTPTransport(timeout=10.0)
    holder_store = ShardStore(retain=2)
    holder.attach_shard_store(holder_store)
    for s in encode_stream(meta, bufs, 2, 1, step=max_step):
        holder_store.put(s)

    applied: Dict[str, Any] = {}
    transport = MagicMock()
    transport.serves_all_donors = True
    transport.metadata.return_value = "http://healer:0"
    transport.recv_checkpoint.side_effect = AssertionError(
        "prefer mode must not touch the donor path when shards cover"
    )
    transport.materialize.side_effect = lambda m, b: unflatten_state_dict(m, b)
    client = MagicMock()
    client.should_commit.return_value = True
    try:
        manager, _, _ = make_manager(
            store,
            client_mock=client,
            checkpoint_transport=transport,
            load_state_dict=lambda sd: applied.update(sd),
            state_dict=lambda: applied,
        )
        assert manager._ec is not None and manager._ec.config.mode == "prefer"
        manager._ec._resolve_peer = None
        client._quorum.return_value = _heal_quorum(max_step, [holder.metadata()])
        manager.start_quorum()
        manager.wait_quorum()
        assert manager.errored() is None
        assert manager.should_commit() is True
        transport.recv_checkpoint.assert_not_called()
        np.testing.assert_array_equal(
            np.asarray(applied["w"]), donor_tree["user"]["default"]["w"]
        )
    finally:
        manager.shutdown()
        holder.shutdown()


# ---------------------------------------------------------------------------
# Full e2e: kill + restart heals through EC when every donor fetch dies
# ---------------------------------------------------------------------------


def test_ec_heal_e2e_donors_unreachable() -> None:
    """Three replica groups with the EC plane on; group 0 is killed
    mid-run and its restarted incarnation's DONOR fetch path is broken
    entirely (the donor-wave stand-in) — healing must complete through
    erasure reconstruction, and all groups converge bitwise."""
    import os

    from torchft_tpu._native import LighthouseServer

    from harness import FailureInjector, Runner, run_replicas
    from test_integ import ddp_train_loop

    prior = {
        k: os.environ.get(k)
        for k in ("TPUFT_EC_K", "TPUFT_EC_M", "TPUFT_HEAL_BACKOFF_BASE_S",
                  "TPUFT_HEAL_BACKOFF_CAP_S")
    }
    os.environ["TPUFT_EC_K"] = "2"
    os.environ["TPUFT_EC_M"] = "1"
    os.environ["TPUFT_HEAL_BACKOFF_BASE_S"] = "0.05"
    os.environ["TPUFT_HEAL_BACKOFF_CAP_S"] = "0.2"
    lighthouse = LighthouseServer(
        bind="[::]:0", min_replicas=3, join_timeout_ms=2000
    )
    orig_recv = HTTPTransport.recv_checkpoint
    broken_fetches: List[int] = []

    def breaking_recv(self, src_rank, metadata, step, timeout):
        if getattr(self, "_ec_test_break", False) and step > 0:
            broken_fetches.append(step)
            raise RuntimeError("injected: donor set unreachable")
        return orig_recv(self, src_rank, metadata, step, timeout)

    HTTPTransport.recv_checkpoint = breaking_recv
    orig_reconstruct = ECPlane.reconstruct_state
    reconstructions: List[int] = []

    def counting_reconstruct(self, step, timeout):
        out = orig_reconstruct(self, step, timeout)
        reconstructions.append(step)
        return out

    ECPlane.reconstruct_state = counting_reconstruct
    try:
        failure = FailureInjector().fail_at(0, 3)

        # Read once, before the runners' threads start: a thread that read it
        # after its sibling had patched would "restore" the patch for good.
        orig_init = HTTPTransport.__init__

        def loop(runner, rank, **kw):
            # Arm the donor-path break for the victim group only: its
            # restarted incarnation must heal via shards.
            if runner.replica_id == 0:
                def marked_init(tself, *a, **k):
                    orig_init(tself, *a, **k)
                    tself._ec_test_break = True
                HTTPTransport.__init__ = marked_init
            try:
                return ddp_train_loop(runner, rank, **kw)
            finally:
                HTTPTransport.__init__ = orig_init

        runners = [
            Runner(
                replica_id=i,
                lighthouse_address=lighthouse.address(),
                failure_injector=failure if i == 0 else FailureInjector(),
                train_loop=loop,
                num_replicas=3,
                attempts=2,
                train_loop_args={"total_steps": 6},
            )
            for i in range(3)
        ]
        results = run_replicas(runners)
        assert failure.count == 1
        # The victim's donor path really died, and healing really went
        # through a shard reconstruction (not a silent donor retry).
        assert broken_fetches, "the donor-path break never armed"
        assert reconstructions, "no erasure reconstruction happened"
        finals = [r[-1] for r in results]
        for other in finals[1:]:
            for key in finals[0]["params"]:
                np.testing.assert_array_equal(
                    np.asarray(finals[0]["params"][key]),
                    np.asarray(other["params"][key]),
                )
    finally:
        HTTPTransport.recv_checkpoint = orig_recv
        ECPlane.reconstruct_state = orig_reconstruct
        lighthouse.shutdown()
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
