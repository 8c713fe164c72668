"""Launcher / restart-supervisor tests.

Reference parity: torchft/torchx.py:11-80 — env plumbing per replica group
and the max_restarts budget; the supervisor itself replaces torchelastic.
The commands under test are tiny python -c scripts so the suite stays fast.
"""

import os
import sys
import time

import pytest

from torchft_tpu.launch import Launcher, main

_PRINT_ENV_AND_SLEEP = (
    "import os,time;"
    "print('gid', os.environ['REPLICA_GROUP_ID'], os.environ['NUM_REPLICA_GROUPS'],"
    " os.environ.get('TPUFT_LIGHTHOUSE',''), flush=True);"
    "time.sleep(60)"
)


def _wait(predicate, timeout: float = 30.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.05)
    raise AssertionError("condition not reached in time")


def test_launcher_env_plumbing_and_restart(tmp_path) -> None:
    """Each group gets REPLICA_GROUP_ID/NUM_REPLICA_GROUPS/TPUFT_LIGHTHOUSE;
    a SIGKILLed group is respawned by supervise_once (the --max_restarts
    analogue, torchft/torchx.py:54)."""
    with Launcher(
        [sys.executable, "-c", _PRINT_ENV_AND_SLEEP],
        num_groups=2,
        lighthouse="embed",
        max_restarts=3,
        log_dir=str(tmp_path),
    ) as launcher:
        assert launcher.lighthouse_address
        _wait(lambda: all(
            (tmp_path / f"g{g}.log").exists()
            and b"gid" in (tmp_path / f"g{g}.log").read_bytes()
            for g in (0, 1)
        ))
        # Fault injection: SIGKILL group 1, no hold -> supervisor respawns it.
        launcher.kill(1, hold=False)
        assert launcher.supervise_once() == [1]
        assert launcher.restarts(1) == 1
        _wait(lambda: (tmp_path / "g1.log").read_bytes().count(b"gid") >= 2)

    log0 = (tmp_path / "g0.log").read_text()
    assert f"gid 0 2 {launcher.lighthouse_address}" in log0


def test_launcher_creates_log_dir(tmp_path) -> None:
    """A nonexistent --log-dir is created, not a FileNotFoundError at the
    first spawn (regression: the CLI died before starting any group)."""
    log_dir = tmp_path / "nested" / "logs"
    with Launcher(
        [sys.executable, "-c", "print('ok')"],
        num_groups=1,
        lighthouse="embed",
        log_dir=str(log_dir),
    ):
        _wait(lambda: (log_dir / "g0.log").exists())


def test_launcher_hold_and_budget(tmp_path) -> None:
    """kill() with hold keeps the supervisor's hands off until spawn();
    an exhausted restart budget is reported, not retried."""
    with Launcher(
        [sys.executable, "-c", "import time; time.sleep(60)"],
        num_groups=1,
        lighthouse="127.0.0.1:1",  # never dialed: command ignores it
        max_restarts=0,
        log_dir=str(tmp_path),
    ) as launcher:
        launcher.kill(0)  # hold=True default
        assert launcher.supervise_once() == []  # held: not restarted
        launcher.spawn(0)  # caller-controlled respawn clears the hold
        _wait(lambda: launcher.running())
        launcher.kill(0, hold=False)
        assert launcher.supervise_once() == []  # budget (0) exhausted
        assert launcher.exhausted() == [0]


def test_launch_cli_clean_exit(tmp_path) -> None:
    """The CLI supervises to completion and exits 0 when every group does."""
    rc = main(
        [
            "--groups",
            "2",
            "--log-dir",
            str(tmp_path),
            "--",
            sys.executable,
            "-c",
            "import os; print('done', os.environ['REPLICA_GROUP_ID'], flush=True)",
        ]
    )
    assert rc == 0
    for g in (0, 1):
        assert f"done {g}" in (tmp_path / f"g{g}.log").read_text()


def test_launch_cli_requires_command() -> None:
    with pytest.raises(SystemExit):
        main(["--groups", "1", "--"])


_SPARE_AWARE = (
    "import os,time;"
    "gid = os.environ.get('REPLICA_GROUP_ID');"
    "sf = os.environ.get('TPUFT_SPARE_FILE');\n"
    "if gid is None and sf:\n"
    "    print('spare ready', flush=True)\n"
    "    while not os.path.exists(sf): time.sleep(0.02)\n"
    "    gid = open(sf).read().strip()\n"
    "print('gid', gid, flush=True); time.sleep(60)"
)


def test_hot_spare_adoption(tmp_path) -> None:
    """A killed group is restarted by handing its id to a ready spare (same
    pid as the former spare — adoption, not a cold fork) and the pool is
    refilled; without the pool the group would pay the full spawn cost."""
    with Launcher(
        [sys.executable, "-c", _SPARE_AWARE],
        num_groups=1,
        lighthouse=None,
        max_restarts=3,
        log_dir=str(tmp_path),
        spares=1,
    ) as launcher:
        _wait(lambda: b"gid 0" in (tmp_path / "g0.log").read_bytes())
        _wait(lambda: launcher.spare_count() == 1)
        spare_pid = launcher._spares[0].proc.pid
        spare_sid = launcher._spares[0].sid

        launcher.kill(0, hold=False)
        assert launcher.supervise_once() == [0]
        # Adoption: the group's process IS the former spare.
        assert launcher._groups[0].proc.pid == spare_pid
        _wait(
            lambda: b"gid 0"
            in (tmp_path / f"spare_{spare_sid}.log").read_bytes()
        )
        # The pool was refilled with a fresh spare.
        _wait(lambda: launcher.spare_count() == 1)
        assert launcher._spares[0].sid != spare_sid


def test_dump_spec_renders_env_contract(capsys) -> None:
    """--dump-spec emits a JobSet manifest carrying the exact launch +
    multihost env contract (reference analogue: the torchx component's
    roles/env, torchft/torchx.py:47-80)."""
    import yaml

    rc = main(
        [
            "--groups", "3",
            "--max-restarts", "7",
            "--dump-spec",
            "--name", "myjob",
            "--hosts-per-group", "4",
            "--image", "gcr.io/proj/img:1",
            "--tpu-topology", "4x4",
            "--",
            "python", "train.py", "--steps", "100",
        ]
    )
    assert rc == 0
    spec = yaml.safe_load(capsys.readouterr().out)

    assert spec["kind"] == "JobSet"
    assert spec["metadata"]["name"] == "myjob"
    assert spec["spec"]["failurePolicy"]["maxRestarts"] == 7
    jobs = {j["name"]: j for j in spec["spec"]["replicatedJobs"]}
    assert set(jobs) == {"lighthouse", "group"}

    group = jobs["group"]
    assert group["replicas"] == 3
    jspec = group["template"]["spec"]
    # Indexed completion IS the host rank; one pod per host.
    assert jspec["completionMode"] == "Indexed"
    assert jspec["completions"] == jspec["parallelism"] == 4
    container = jspec["template"]["spec"]["containers"][0]
    env = {e["name"]: e for e in container["env"]}
    assert env["NUM_REPLICA_GROUPS"]["value"] == "3"
    assert env["TPUFT_NUM_HOSTS"]["value"] == "4"
    assert "myjob-lighthouse-0-0.myjob" in env["TPUFT_LIGHTHOUSE"]["value"]
    assert "job-index" in str(env["TPUFT_GROUP_INDEX"]["valueFrom"])
    # TPUFT_SLICE_GEN's source: the JobSet restart-attempt annotation via
    # the downward API — nothing injects a JOBSET_RESTART_ATTEMPT env var,
    # so without this fieldRef the generation would always read 0.
    assert "restart-attempt" in str(env["JOBSET_RESTART_ATTEMPT"]["valueFrom"])
    script = container["args"][0]
    # The shell prologue derives the rest of the contract per pod.  The
    # store DNS name must be the 4-component JobSet pod name of the group's
    # host-rank-0 pod (<jobset>-<job>-<jobindex>-<podindex>.<jobset>), and
    # rank 0 must actually SERVE the store (initialize_slice is a client).
    for line in (
        'REPLICA_GROUP_ID="${TPUFT_GROUP_INDEX}"',
        'TPUFT_HOST_RANK="${JOB_COMPLETION_INDEX}"',
        'TPUFT_STORE="myjob-group-${REPLICA_GROUP_ID}-0.myjob:29500"',
        "python -m torchft_tpu.store_cli",
        'MASTER_ADDR="myjob-group-${REPLICA_GROUP_ID}-0.myjob"',
        "TPUFT_SLICE_GEN=",
        "exec python train.py --steps 100",
    ):
        assert line in script, script
    # TPU slice placement.
    pod = jspec["template"]["spec"]
    assert pod["nodeSelector"]["cloud.google.com/gke-tpu-topology"] == "4x4"
    assert container["resources"]["limits"]["google.com/tpu"] == 4

    lighthouse = jobs["lighthouse"]
    lcmd = lighthouse["template"]["spec"]["template"]["spec"]["containers"][0]["command"]
    assert "torchft_tpu.lighthouse_cli" in lcmd


def test_crash_loop_backoff(tmp_path) -> None:
    """A group that exits nonzero almost immediately is restarted with
    exponential backoff, not at the supervisor's poll rate (ADVICE r3:
    unbounded ~4 restarts/s on an instant-fail command)."""
    with Launcher(
        [sys.executable, "-c", "raise SystemExit(3)"],
        num_groups=1,
        lighthouse=None,
        max_restarts=None,
        log_dir=str(tmp_path),
    ) as launcher:
        _wait(lambda: launcher._groups[0].proc.poll() is not None)
        # Tight supervision loop for 1.2s: without the brake this would
        # restart ~5 times (0.25s/attempt incl. spawn); with 0.5s doubling
        # backoff at most 2 restarts fit.
        deadline = time.monotonic() + 1.2
        while time.monotonic() < deadline:
            launcher.supervise_once()
            time.sleep(0.02)
        assert launcher.restarts(0) <= 2
        # And the brake does not wedge the supervisor: ANOTHER restart still
        # lands once its (longer) backoff expires.
        before = launcher.restarts(0)
        _wait(
            lambda: (launcher.supervise_once(), launcher.restarts(0) > before)[1],
            timeout=10.0,
        )


def test_group_env_and_compile_cache_reach_the_children(tmp_path, monkeypatch) -> None:
    """`group_env` gives each group what it owns alone (on a TPU host: the
    runtime's visibility settings that confine it to its own chip), on the
    first spawn and on every respawn; and every child inherits the one
    compile-cache location under JAX's own variable — the one the
    environment already names, else `<repo>/.jax_cache`."""
    from torchft_tpu.launch import export_compile_cache

    # beside the place: the key holds the programs' metadata (the model's scopes) and no source line
    key = {"JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY": "1", "JAX_TRACEBACK_IN_LOCATIONS_LIMIT": "0"}
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else", "JAX_TRACEBACK_IN_LOCATIONS_LIMIT": "10"}
    assert export_compile_cache(env) == "/somewhere/else"
    assert env == dict(key, JAX_COMPILATION_CACHE_DIR="/somewhere/else", JAX_TRACEBACK_IN_LOCATIONS_LIMIT="10")
    env = {}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert export_compile_cache(env) == os.path.join(repo, ".jax_cache")
    assert env == dict(key, JAX_COMPILATION_CACHE_DIR=os.path.join(repo, ".jax_cache"))

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    show = (
        "import os,time;"
        "print('chip', os.environ.get('TPU_VISIBLE_CHIPS'),"
        " os.environ['JAX_COMPILATION_CACHE_DIR'], flush=True);"
        "time.sleep(60)"
    )
    with Launcher(
        [sys.executable, "-c", show],
        num_groups=2,
        lighthouse="127.0.0.1:1",  # never dialed: the command ignores it
        max_restarts=1,
        log_dir=str(tmp_path),
        group_env={g: {"TPU_VISIBLE_CHIPS": str(g)} for g in (0, 1)},
    ) as launcher:
        _wait(lambda: all(
            (tmp_path / f"g{g}.log").exists()
            and b"chip" in (tmp_path / f"g{g}.log").read_bytes()
            for g in (0, 1)
        ))
        launcher.kill(1, hold=False)
        assert launcher.supervise_once() == [1]
        _wait(lambda: (tmp_path / "g1.log").read_bytes().count(b"chip") >= 2)
    cache = os.path.join(repo, ".jax_cache")
    assert (tmp_path / "g0.log").read_text().splitlines() == [f"chip 0 {cache}"]
    assert (tmp_path / "g1.log").read_text().splitlines() == [f"chip 1 {cache}"] * 2
