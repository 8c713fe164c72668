"""Multi-host slice bootstrap: rendezvous through the group Store."""

import os
import socket
import subprocess
import sys
import threading

import pytest

from torchft_tpu.coordination import StoreServer
from torchft_tpu.multihost import (
    SliceConfig,
    initialize_slice,
    slice_config_from_env,
)


def test_config_from_env_defaults() -> None:
    cfg = slice_config_from_env(env={})
    assert cfg.host_rank == 0 and cfg.num_hosts == 1
    assert not cfg.is_multihost


def test_single_host_is_noop() -> None:
    calls = []
    out = initialize_slice(
        SliceConfig(host_rank=0, num_hosts=1, store_addr=None),
        _initialize=lambda **kw: calls.append(kw),
    )
    assert out is None and calls == []


def test_multihost_requires_store() -> None:
    with pytest.raises(RuntimeError, match="TPUFT_STORE"):
        initialize_slice(
            SliceConfig(host_rank=0, num_hosts=2, store_addr=None),
            _initialize=lambda **kw: None,
        )


def test_rendezvous_all_hosts_agree() -> None:
    """4 'hosts' (threads) rendezvous through one real StoreServer; every
    jax.distributed.initialize call must get the same coordinator, the
    right process_id, and num_processes=4."""
    server = StoreServer(bind="127.0.0.1:0")
    try:
        calls = {}
        lock = threading.Lock()

        def host(rank: int):
            def fake_init(coordinator_address, num_processes, process_id):
                with lock:
                    calls[process_id] = (coordinator_address, num_processes)

            initialize_slice(
                SliceConfig(
                    host_rank=rank,
                    num_hosts=4,
                    store_addr=server.address(),
                    coord_port=9999,
                ),
                key_prefix="test_slice",
                _initialize=fake_init,
            )

        threads = [threading.Thread(target=host, args=(r,)) for r in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert sorted(calls) == [0, 1, 2, 3]
        coords = {c for c, _ in calls.values()}
        assert len(coords) == 1, f"hosts disagree on coordinator: {coords}"
        assert all(n == 4 for _, n in calls.values())
        assert next(iter(coords)).endswith(":9999")

        # Restart incarnation: generation 1 must NOT read generation 0's
        # (stale) coordinator from the still-live store.
        got = {}

        def host2(rank: int):
            initialize_slice(
                SliceConfig(
                    host_rank=rank,
                    num_hosts=2,
                    store_addr=server.address(),
                    coord_port=7777,
                    generation=1,
                ),
                key_prefix="test_slice",
                _initialize=lambda coordinator_address, num_processes, process_id: got.setdefault(
                    process_id, coordinator_address
                ),
            )

        threads = [threading.Thread(target=host2, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert sorted(got) == [0, 1]
        assert all(c.endswith(":7777") for c in got.values()), got
    finally:
        server.shutdown()


_CHILD = r"""
import os, sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.environ["TPUFT_REPO"])

from torchft_tpu.multihost import initialize_slice

coordinator = initialize_slice()  # REAL jax.distributed.initialize

import jax

assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2 * jax.local_device_count()
# One cross-process sanity value through the distributed runtime: both
# processes agree on the global device set.
ids = sorted(d.process_index for d in jax.devices())
assert ids[0] == 0 and ids[-1] == 1, ids
print("OK", os.environ["TPUFT_HOST_RANK"], coordinator, flush=True)
jax.distributed.shutdown()
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_pair(store_addr: str, generation: int, coord_port: int):
    """Two real OS processes bootstrap one slice through the live Store."""
    procs = []
    for rank in (0, 1):
        env = dict(
            os.environ,
            TPUFT_REPO=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            TPUFT_HOST_RANK=str(rank),
            TPUFT_NUM_HOSTS="2",
            TPUFT_STORE=store_addr,
            TPUFT_COORD_PORT=str(coord_port),
            TPUFT_SLICE_GEN=str(generation),
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _CHILD],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=120)
        outs.append(out)
        assert p.returncode == 0, f"child failed:\n{out}"
    return outs


def test_two_real_processes_rendezvous_and_restart_generation() -> None:
    """No monkeypatched jax.distributed: two actual CPU-JAX processes
    rendezvous through a real StoreServer, initialize one 2-process JAX
    runtime, and agree on the global device set.  The slice then 'dies'
    (both processes exit) and the supervisor restarts it as generation 1:
    the gen-0 coordinator key is still in the long-lived store, and the
    restarted pair must rendezvous on the NEW key/port, not dial the dead
    coordinator."""
    server = StoreServer(bind="127.0.0.1:0")
    try:
        port0 = _free_port()
        outs0 = _run_pair(server.address(), generation=0, coord_port=port0)
        assert any(f":{port0}" in o for o in outs0), outs0

        # Restart incarnation: a DIFFERENT coordinator port proves the pair
        # read gen1's key; dialing the stale gen-0 coordinator would hang
        # (nothing listens there anymore) and time out.
        port1 = _free_port()
        outs1 = _run_pair(server.address(), generation=1, coord_port=port1)
        assert any(f":{port1}" in o for o in outs1), outs1
        for out in outs1:
            assert f":{port0}" not in out
    finally:
        server.shutdown()
