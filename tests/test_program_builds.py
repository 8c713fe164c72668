"""Program builds (obs/builds.py): one record a stage of every program JAX
builds, from JAX's own events, on the sub-spans' clock; kept in the process's
ring, taken by a tracker with a stream and written with its next
``step_summary``; tagged where ``TrainStep`` knows the program."""

import json
import os
import re
import subprocess
import sys
import time
from unittest.mock import MagicMock

import jax
import jax.numpy as jnp
import pytest

from test_manager import make_manager, make_quorum, store  # noqa: F401
from test_subspans import records

from torchft_tpu.metrics import EVENTS
from torchft_tpu.obs import builds
from torchft_tpu.obs.spans import SUBSPANS, SpanTracker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def listening():
    """The listeners on — as often as asked, once — and the ring empty of what
    earlier tests of this process built."""
    for _ in range(3):
        builds.register()
    builds.take()
    yield
    builds.take()


class KeepingFile:
    """Stands in for the logger's raw file: keeps what each ``write()`` was handed."""

    def __init__(self, raw):
        self.raw, self.written = raw, []

    def write(self, data):
        self.written.append(bytes(data))
        return self.raw.write(data)

    def close(self):
        self.raw.close()


def of(recs, fun):
    """The records of the function `fun`: its trace stage goes by the Python
    name, its lower and backend stages by the module's (``jit(fun)``)."""
    return [r for r in recs if r["fun_name"] in (fun, f"jit({fun})")]


def test_first_call_leaves_three_stages_and_the_second_none() -> None:
    def tripled(x):
        return x * 3.0

    f = jax.jit(tripled)
    before = time.monotonic_ns()
    f(jnp.ones(8)).block_until_ready()
    after = time.monotonic_ns()
    mine = of(builds.take(), "tripled")
    assert [r["stage"] for r in mine] == ["trace", "lower", "backend"]  # once each, however often registered
    for r in mine:
        assert before <= r["t0_ns"] < r["t1_ns"] <= after  # time.monotonic_ns, the sub-spans' clock
        assert r["program"] is None and r["outer"] is None and r["step"] is None
        assert r["thread"] == "MainThread"
    assert [r["t1_ns"] for r in mine] == sorted(r["t1_ns"] for r in mine)
    assert mine[2]["cache"] in ("hit", "miss", "off") and "cache" not in mine[0]
    f(jnp.ones(8)).block_until_ready()
    assert of(builds.take(), "tripled") == []


@pytest.mark.parametrize("short_s", [0.0, 3600.0])
def test_a_jit_traced_inside_anothers_trace_names_its_outer(short_s, monkeypatch) -> None:
    """With no stage short enough to fold, every nested one is kept under its
    outermost stage's name; with every nested trace short enough, the
    outermost stage's record counts them."""
    monkeypatch.setattr(builds, "SHORT_S", short_s)

    @jax.jit
    def kernel_like(x):
        return jnp.sin(x) * 2.0

    def enclosing(x):
        return kernel_like(x) + 1.0

    jax.jit(enclosing)(jnp.ones(8)).block_until_ready()
    recs = builds.take()
    outer = {r["stage"]: r for r in of(recs, "enclosing")}
    assert set(outer) == {"trace", "lower", "backend"} and outer["trace"]["outer"] is None
    nested = [r for r in recs if r["outer"] == "enclosing"]
    if short_s:
        # kernel_like, its sin and multiply, the add: four at least, however jnp splits them
        assert nested == [] and outer["trace"]["nested_short"] >= 4
        assert 0 < outer["trace"]["nested_short_s"] and "nested_short" not in outer["lower"]
        return
    (inner,) = of(recs, "kernel_like")  # inlined: a trace stage and no build of its own
    assert inner["stage"] == "trace" and inner["outer"] == "enclosing" and "nested_short" not in outer["trace"]
    # Inside the outer's interval, so a union of intervals counts it once.
    assert outer["trace"]["t0_ns"] <= inner["t0_ns"] < inner["t1_ns"] <= outer["trace"]["t1_ns"]
    # What jnp's own jitted functions leave inside it names the outermost stage too.
    assert {r["fun_name"] for r in nested} >= {"kernel_like", "sin", "multiply"}


CACHE_SCRIPT = """
import json, sys
from torchft_tpu.obs import builds
builds.register()
assert "jax" not in sys.modules and not builds._registered  # a JAX-free process stays one
import jax, jax.numpy as jnp
builds.register()
def cached(x):
    return jnp.cos(x) + 5.0
out = []
for _ in range(2):
    jax.jit(cached)(jnp.ones(16)).block_until_ready()
    out.append([r for r in builds.take() if r["fun_name"] == "jit(cached)" and r["stage"] == "backend"])
    jax.clear_caches()
print(json.dumps(out))
"""


def test_the_persistent_cache_says_miss_then_hit(tmp_path) -> None:
    """One process, the in-memory caches cleared between two builds of one
    function: the first compiles and writes the entry, the second loads it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=REPO)
    proc = subprocess.run([sys.executable, "-c", CACHE_SCRIPT], env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (first,), (second,) = json.loads(proc.stdout.strip().splitlines()[-1])
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert second["cache"] == "hit" and second["retrieval_s"] >= 0 and "saved_s" in second
    assert second["t1_ns"] - second["t0_ns"] >= second["retrieval_s"] * 1e9


def test_builds_before_a_manager_leave_with_its_first_summary_in_one_write(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    def before_manager(x):
        return x - 1.0

    def during_step(x):
        return x - 2.0

    jax.jit(before_manager)(jnp.ones(4)).block_until_ready()
    path = tmp_path / "stream.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    client = MagicMock()
    client._quorum.return_value = make_quorum()
    client.should_commit.return_value = True
    manager, _, _ = make_manager(store, client_mock=client)
    try:
        file = manager.metrics._file = KeepingFile(manager.metrics._file)
        assert records(path) == []  # a Manager's start writes nothing: the builds and its own sub-span wait
        manager.start_quorum()
        jax.jit(during_step)(jnp.ones(4)).block_until_ready()
        assert manager.should_commit()
        recs = records(path)
        at = [r["event"] for r in recs].index("step_summary")
        tail = recs[at:]
        assert [r["event"] for r in tail[:2]] == ["step_summary", "subspan"]
        assert {r["event"] for r in tail[2:]} == {"program_build"}
        (one,) = [w for w in file.written if b'"step_summary"' in w]  # summary, sub-spans and builds in ONE write()
        assert one.count(b"\n") == len(tail) and one.count(b'"program_build"') == len(tail) - 2
        assert len({(r["ts"], r["t_mono"]) for r in tail}) == 1
        written = [r for r in tail if r["event"] == "program_build"]
        assert [(r["stage"], r["step"]) for r in of(written, "before_manager")] == [
            ("trace", None), ("lower", None), ("backend", None)]
        assert [(r["stage"], r["step"]) for r in of(written, "during_step")] == [
            ("trace", 0), ("lower", 0), ("backend", 0)]
        assert all(r["slice_gen"] == 0 and "unregistered" not in r for r in written)
        assert builds.records() == []  # taken
        (start,) = [s for s in tail[1]["spans"] if s["name"] == "manager_start"]
        assert start["parent"] is None and start["step"] == 0 and 0 < start["t1_ns"] - start["t0_ns"] < 60e9
        assert "manager_start" not in tail[0]["phases"]  # a sub-span: in no step's phases or ledger
        # A build after the vote leaves at shutdown, with the next step's number.
        jax.jit(lambda x: x - 3.0)(jnp.ones(4)).block_until_ready()
    finally:
        manager.shutdown()
    late = records(path, "program_build")[len(written):]
    assert late and {r["step"] for r in late} == {1}
    assert [r["fun_name"] for r in late if r["stage"] == "backend"] == ["jit(<lambda>)"]


def test_without_a_stream_nothing_is_written_and_the_ring_stays_bounded(store, monkeypatch) -> None:  # noqa: F811
    monkeypatch.delenv("TPUFT_METRICS_PATH", raising=False)
    client = MagicMock()
    client._quorum.return_value = make_quorum()
    client.should_commit.return_value = True
    manager, _, _ = make_manager(store, client_mock=client)
    try:
        assert not manager.metrics.enabled
        jax.jit(lambda x: x - 4.0)(jnp.ones(4)).block_until_ready()
        held = len(builds.records())
        assert held >= 3
        manager.start_quorum()
        assert manager.should_commit()
        assert len(builds.records()) == held  # nobody took them
    finally:
        manager.shutdown()
    assert len(builds.records()) == held
    trace = next(event for event, stage in builds.STAGES.items() if stage == "trace")
    for i in range(builds.RING + 50):  # JAX's own calls, by hand
        builds._on_start(trace, 0.0, fun_name=f"f{i}")
        builds._on_duration(trace, 1e-6, fun_name=f"f{i}")
    kept = builds.records()
    assert len(kept) == builds.RING and kept[-1]["fun_name"] == f"f{builds.RING + 49}"  # the newest stay


@pytest.mark.parametrize("call, programs", [
    ("split", {"jit_value_and_grad": ["value_and_grad", "jit(value_and_grad)", "jit(value_and_grad)"],
               "jit_apply": ["apply", "jit(apply)", "jit(apply)"]}),
    ("full", {"jit_full": ["full", "jit(full)", "jit(full)"]}),
])
def test_trainsteps_programs_carry_their_tag(call, programs) -> None:
    import optax

    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[jax.devices()[0]])
    step = TrainStep(ftmesh, optax.sgd(0.1), lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2))
    params, batch = {"w": jnp.ones((4, 4))}, {"x": jnp.ones((2, 4))}
    opt = step.init_opt_state(params)
    builds.take()
    if call == "split":
        _, grads = step.grads(params, batch)
        params, opt = step.apply(params, opt, grads)  # donated: the new ones from here on
        assert set(step._ran) == {"value_and_grad", "apply"}  # `op_map`'s own note is as it was
    else:
        step.full_step(params, opt, batch)
    recs = builds.take()
    tagged = {p: [r for r in recs if r["program"] == p] for p in {r["program"] for r in recs} - {None}}
    assert {p: [r["fun_name"] for r in rs] for p, rs in tagged.items()} == programs
    assert all([r["stage"] for r in rs] == ["trace", "lower", "backend"] for rs in tagged.values())
    # Everything else keeps JAX's name and no tag; what was traced inside names the program's
    # function, or — under a millisecond — is counted on its trace stage.
    inside = [r for r in recs if r["program"] is None and r["outer"] is not None]
    assert {r["outer"] for r in inside} <= {rs[0]["fun_name"] for rs in tagged.values()}
    for trace, _, _ in tagged.values():
        assert [r for r in inside if r["outer"] == trace["fun_name"]] or trace.get("nested_short", 0) > 0
    # The same functions again, same shapes: no trace, no record.
    if call == "split":
        step.grads(params, batch)
        assert [r for r in builds.take() if r["program"]] == []


def test_the_new_names_are_registered_where_the_pins_look() -> None:
    """`program_build` in the registry under a literal ``emit("program_build"``
    call site (what tests/test_obs.py greps) and `manager_start` among the
    sub-spans under a literal ``note_sub("manager_start"`` in manager.py (what
    tests/test_subspans.py greps)."""
    import torchft_tpu

    pkg = os.path.dirname(torchft_tpu.__file__)
    assert "program_build" in EVENTS and "manager_start" in SUBSPANS and SUBSPANS["manager_start"] is None
    with open(os.path.join(pkg, "obs", "spans.py"), encoding="utf-8") as f:
        assert re.search(r"\.emit\(\s*\"program_build\"", f.read())
    with open(os.path.join(pkg, "manager.py"), encoding="utf-8") as f:
        assert re.search(r"note_sub\(\s*\"manager_start\"", f.read())
    # A tracker whose logger is off takes nothing out of the ring.
    builds._on_duration(next(iter(builds.STAGES)), 1e-6, fun_name="kept")
    tracker = SpanTracker(MagicMock(enabled=False))
    tracker.step_summary(0, committed=True)
    tracker.flush_subspans()
    assert [r["fun_name"] for r in builds.records()] == ["kept"]
