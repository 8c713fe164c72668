#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the main path once on a TPU through the library's own entry points
(`Launcher`, `LighthouseServer`, `Manager`, `TrainStep.ft_step`,
`GradientAverager`, `TCPCollective`, `HTTPTransport`) at the repo's own two
widths (`flagship_config()`, `large_config()` below), checks what comes out by
the repo's own means, and fails loudly when any part does not.

    python chip_smoke.py               one chip:   train, heal, large
    python chip_smoke.py --four-chips  four chips: replicas, mesh (and what
                                       each is compared with; none of the above)

One process per chip.  The parent never initialises a JAX backend (asserted
at the end): it loads the native library, then runs the phases one after
another, each in children that own the chip alone and exit before the next
starts.  `train` and `replicas` are started through the `Launcher` with this
script's own worker mode as the group command — the library is the entry
point, this script is the user's train loop.  Every child asserts the TPU
platform before anything else; no phase catches a failure and carries on;
any failed phase makes the script exit non-zero and print no result.

Each phase prints one JSON object (smoke numbers: a handful of steps on one
fixed batch, NOT benchmark results); the last line of stdout is the
contract's `{"ok": true, "device": {...}}`.

The phase bodies are functions of the model and the required platform, so
`tests/test_chip_smoke.py` rehearses them at a tiny width on virtual CPU
devices; the command line has no such option.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_ROOT = os.path.join(REPO, "chiprun_out", "chip_smoke")

# Tolerances, stated once.  The kernels and the XLA formulation run the same
# bf16 matmuls with f32 accumulation but sum in another order, so they agree
# to a few bf16 ulps (2**-8 = 0.4%) accumulated over 12 layers, not bitwise.
LOSS_RTOL = 1e-2  # |loss_kernels - loss_xla| <= LOSS_RTOL * |loss_xla|
GRAD_REL_L2 = 5e-2  # ||g_kernels - g_xla|| <= GRAD_REL_L2 * ||g_xla||, whole tree
MESH_LOSS_RTOL = 1e-2  # fsdp x tensor mesh loss vs the one-chip loss
# Averaged gradient vs the float32 mean of the local ones, per wire dtype:
# relative to the largest |mean| of the leaf (f32 wire: summation order only).
WIRE_TOL = {"f32": 1e-5, "bf16": 2.0**-7}

# Worker step budget: stop at the first step >= MIN_STEPS whose last
# TAIL_MERGED commits all had every group participating.  With one group
# that is MIN_STEPS steps; with four it leaves room for the kill (at
# KILL_AFTER merged steps) and demands TAIL_MERGED merged steps after the
# heal, on every group at the same step.
MIN_STEPS = 8
KILL_AFTER = 6
MIN_STEPS_REPLICAS = 10
TAIL_MERGED = 3


@dataclasses.dataclass(frozen=True)
class Model:
    """One width of the repo's transformer and how it is trained."""

    cfg: Any  # torchft_tpu.models.TransformerConfig
    batch_size: int
    seq: int
    optimizer: str  # "adamw" | "adafactor"

    def tx(self):
        import optax

        return {"adamw": optax.adamw, "adafactor": optax.adafactor}[self.optimizer](3e-4)


def flagship_config():
    """The repo's 134M "flagship" width: (TransformerConfig, batch_size, seq).
    Not a cell of the benchmark (PERF.md section 4); this script and
    tools/profile_step.py run it, tests/test_chip_compile.py compiles it."""
    from torchft_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=32000,
        d_model=768,
        n_layers=12,
        # head_dim 128 = TPU lane width: the pallas flash-attention kernel
        # engages (d_head 64 falls back to XLA S^2 attention) and MXU tiles
        # are full.  Measured on v5e: 12 heads x 64 -> 273 ms/step, 6 x 128
        # -> 213 ms at identical param count (rounds 1-3).
        n_heads=6,
        n_kv_heads=6,
        d_ff=2048,
        max_seq=1024,
        # 134M params at batch 16 fits HBM without rematerialization; remat
        # would recompute every layer in backward (~4/3 the FLOPs) to save
        # memory this config doesn't need.
        remat=False,
        # Full unroll of the layer stack: XLA fuses/pipelines across layer
        # boundaries, and >= n_layers takes the static-Python-loop path
        # (constant-folded layer indexing — kills ~17 ms/step of
        # dynamic-update-slice grad writes the scan form leaves behind).
        # Measured on v5e at this config: scan 158 ms/step -> scan-unroll
        # 141 ms -> static loop 131 ms (round 3; now 108 ms with the
        # round-4 pallas backward + fused CE).  Partial unroll (4) was
        # slower than any of these.
        scan_unroll=12,
    )
    return cfg, 16, 1024


def large_config():
    """The scale-proof model: ~1B params, the largest round shape that fits
    one v5e chip (16 GB HBM) with f32 params + a memory-lean factored
    optimizer — withOUT rematerialization, which measured as a pure loss
    at this size (see the remat field comment).  VERDICT r4 #2: show the
    MFU and heal story survive a ~10x model (reference capability chased:
    'train models such as Llama 3 70B', reference README)."""
    from torchft_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=32000,
        d_model=2048,
        n_layers=12,
        n_heads=16,
        n_kv_heads=16,
        d_ff=8192,
        max_seq=1024,
        # Measured on v5e at batch 8: remat 410 ms/step (58.6% MFU) vs
        # NO remat 334 ms (71.9%) — the flash-attention kernels' O(S*D)
        # residuals and the fused CE's never-materialized logits leave
        # enough HBM at this size that paying the recompute tax is a pure
        # loss.  Larger-than-HBM configs flip remat back on.
        remat=False,
        scan_unroll=12,  # static layer loop, same as the flagship
    )
    return cfg, 8, 1024


def flagship() -> Model:
    return Model(*flagship_config(), optimizer="adamw")


def large() -> Model:
    return Model(*large_config(), optimizer="adafactor")


def benchmark():
    """The benchmark's tables and helpers (`BENCHMARK.json`, `benchmark/`):
    the smoke's MFU line is computed from what a cell's would be."""
    from benchmark.spec import Benchmark

    return Benchmark(REPO)


def bf16_peak(device_kind: str) -> float:
    """bf16 FLOP/s of one chip from `benchmark/peaks.json`.  A kind the
    table does not hold is an error, never a guess."""
    return float(benchmark().peaks(device_kind)["bf16_flops_per_s"])


# ---------------------------------------------------------------------------
# Small shared pieces (children only: these import JAX).
# ---------------------------------------------------------------------------


def jax_backend_created() -> bool:
    """True once this process has initialised any JAX backend."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return bool(xb is not None and xb.backends_are_initialized())


def require_platform(platform: str):
    """First thing every child does: the device, or a loud failure."""
    import jax

    device = jax.devices()[0]
    if device.platform != platform:
        raise RuntimeError(
            f"chip_smoke needs platform {platform!r}; JAX found "
            f"{device.platform!r} ({device.device_kind}) — no result"
        )
    return device


def device_report() -> Dict[str, Any]:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def compile_counter():
    """The benchmark's counter of this process's compilations: persistent
    cache hits and misses, and every backend compile."""
    return benchmark().job("steady").CompileCounter()


def cache_report(counter) -> Dict[str, Any]:
    return {
        "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "compile_cache_hits": counter.hits,
        "compile_cache_misses": counter.misses,
        "backend_compiles": counter.compiles,
    }


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def seeded_batch(model: Model, seed: int, sharding=None) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    tokens = rng.integers(
        0, model.cfg.vocab_size, size=(model.batch_size, model.seq)
    ).astype(np.int32)
    batch = {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)}
    if sharding is not None:
        return {k: jax.device_put(v, sharding) for k, v in batch.items()}
    return {k: jnp.asarray(v) for k, v in batch.items()}


def flops_per_step(model: Model) -> float:
    """Operations one training step requires, by the benchmark's count
    (`benchmark/flops/dense_lm.py`: matmul parameters and causal attention;
    the embedding table is a gather and counts nothing)."""
    cfg = model.cfg
    published = {
        "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.d_ff,
        "num_hidden_layers": cfg.n_layers,
        "vocab_size": cfg.vocab_size,
    }
    per_token = benchmark().flops("dense_lm").train_flops_per_token(published, model.seq)
    return per_token * model.batch_size * model.seq


def params_digest(params: Any) -> str:
    sys.path.insert(0, os.path.join(REPO, "examples"))
    try:
        from _common import params_digest as digest
    finally:
        sys.path.pop(0)
    return digest(params)


@contextlib.contextmanager
def xla_formulation():
    """Traces inside see no TPU, so attention takes `_fa_reference` and the
    loss the unfused CE: the plain XLA formulation the kernels are compared
    with.  Steering lives here, in the script; the program has no option."""
    from torchft_tpu.ops import _pallas_util

    saved = _pallas_util.on_tpu
    _pallas_util.on_tpu = lambda: False
    try:
        yield
    finally:
        _pallas_util.on_tpu = saved


KERNELS = ("tpuft_fa_fwd", "tpuft_fa_bwd_dkdv_dq", "tpuft_ce_lse", "tpuft_ce_dlogits")


def has_kernel(compiled_text: str, name: str) -> bool:
    """A `tpu_custom_call` whose op name carries the pallas kernel's `name`."""
    import re

    return any(
        "tpu_custom_call" in line and re.search(rf"\b{name}\b", line)
        for line in compiled_text.splitlines()
    )


def kernels_in(compiled_text: str) -> Dict[str, bool]:
    """Which of the main path's pallas kernels a compiled program contains."""
    return {k: has_kernel(compiled_text, k) for k in KERNELS}


def on_device(tree: Any, device) -> bool:
    """Every leaf a `jax.Array` that lives on `device` and nowhere else."""
    import jax

    return all(
        isinstance(l, jax.Array) and l.devices() == {device} for l in jax.tree.leaves(tree)
    )


def replica_setup(model: Model, device, batch_seed: int):
    """What every one-device replica starts from: (ftmesh, TrainStep, batch,
    state) with the seed's parameters and a fresh optimizer state."""
    import jax

    from torchft_tpu.models import init_params, loss_fn
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    cfg = model.cfg
    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    step = TrainStep(ftmesh, model.tx(), lambda p, b: loss_fn(p, b, cfg))
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = {"params": params, "opt": step.init_opt_state(params)}
    return ftmesh, step, seeded_batch(model, batch_seed), state


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


def make_manager(
    state: Dict[str, Any],
    replica_id: str,
    lighthouse_addr: Optional[str],
    *,
    use_async_quorum: bool,
    on_heal: Optional[Callable[[Any], None]] = None,
):
    """A replica group's Manager with the standard wiring: TCP ring data
    plane + HTTP checkpoint transport.  `init_sync=False`: every group
    starts from the same seed, so the only heal is the one after a failure.
    Returns (manager, transport)."""
    from datetime import timedelta

    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.manager import Manager

    def load(sd) -> None:
        state["params"], state["opt"] = sd["params"], sd["opt"]
        if on_heal is not None:
            on_heal(sd)

    transport = HTTPTransport(timeout=120.0)
    manager = Manager(
        collective=TCPCollective(timeout=120.0),
        load_state_dict=load,
        state_dict=lambda: {"params": state["params"], "opt": state["opt"]},
        min_replica_size=1,
        use_async_quorum=use_async_quorum,
        timeout=timedelta(seconds=120),
        quorum_timeout=timedelta(seconds=180),
        rank=0,
        world_size=1,
        replica_id=replica_id,
        lighthouse_addr=lighthouse_addr,
        checkpoint_transport=transport,
        init_sync=False,
    )
    return manager, transport


def grad_sample(tree: Any, n: int = 4096) -> Dict[str, Any]:
    """A strided sample of every leaf as float32 numpy (a whole gradient is
    half a gigabyte; the sample is what crosses processes)."""
    import jax
    import numpy as np

    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        flat = leaf.reshape(-1)
        stride = max(1, flat.shape[0] // n)
        out[jax.tree_util.keystr(path)] = np.asarray(flat[::stride][:n], dtype=np.float32)
    return out


def compare_to_mean(avg: Dict[str, Any], locals_: Sequence[Dict[str, Any]], wire: str) -> float:
    """max over leaves of |avg - float32 mean(locals)| / max|mean|; raises
    past the wire dtype's tolerance."""
    import numpy as np

    worst = 0.0
    for key, got in avg.items():
        mean = np.mean(np.stack([l[key].astype(np.float32) for l in locals_]), axis=0)
        scale = float(np.max(np.abs(mean))) or 1.0
        worst = max(worst, float(np.max(np.abs(got.astype(np.float32) - mean))) / scale)
    check(
        worst <= WIRE_TOL[wire],
        f"averaged gradient differs from the f32 mean by {worst:.3g} of the "
        f"leaf's largest value (wire {wire}, tolerance {WIRE_TOL[wire]:.3g})",
    )
    return worst


# ---------------------------------------------------------------------------
# train / large: one replica group, ft_step.
# ---------------------------------------------------------------------------


def train_body(
    model: Model,
    platform: str,
    lighthouse_addr: Optional[str],
    *,
    steps: int,
    reference: bool,
) -> Dict[str, Any]:
    """One replica group taking `steps` commit-gated `ft_step`s on one fixed
    seeded batch.  With `reference`, the first-step loss and gradients are
    first compared with the plain XLA formulation and the compiled gradient
    program is searched for the kernels."""
    t_phase = time.perf_counter()
    device = require_platform(platform)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models import loss_fn
    from torchft_tpu.parallel import TrainStep

    cache = compile_counter()
    cfg = model.cfg
    ftmesh, step, batch, state = replica_setup(model, device, batch_seed=0)
    params = state["params"]
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    out: Dict[str, Any] = {"n_params": n_params, "device": device_report()}

    t0 = time.perf_counter()
    compiled = step.lower_grads(params, batch).compile()
    compile_s = time.perf_counter() - t0
    found = kernels_in(compiled.as_text())
    out["kernels_in_gradient_program"] = found
    if platform == "tpu":
        check(all(found.values()), f"a kernel gave way to its reference: {found}")
    out["gradient_program_bytes"] = _memory_analysis(compiled)
    del compiled

    if reference:
        loss_k, grads_k = step.grads(params, batch)
        with xla_formulation():
            ref = TrainStep(ftmesh, model.tx(), lambda p, b: loss_fn(p, b, cfg))
            ref_text = ref.lower_grads(params, batch).compile().as_text()
            check("tpu_custom_call" not in ref_text, "the reference program holds a kernel")
            loss_x, grads_x = ref.grads(params, batch)
        diff2 = sum(
            jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2)
            for a, b in zip(jax.tree.leaves(grads_k), jax.tree.leaves(grads_x))
        )
        ref2 = sum(jnp.sum(b.astype(jnp.float32) ** 2) for b in jax.tree.leaves(grads_x))
        rel = float(jnp.sqrt(diff2 / ref2))
        loss_k, loss_x = float(loss_k), float(loss_x)
        out["first_step_vs_xla"] = {
            "loss_kernels": loss_k,
            "loss_xla": loss_x,
            "loss_rtol": LOSS_RTOL,
            "grad_rel_l2": rel,
            "grad_rel_l2_tol": GRAD_REL_L2,
        }
        check(np.isfinite(loss_k) and np.isfinite(loss_x), "first-step loss not finite")
        check(abs(loss_k - loss_x) <= LOSS_RTOL * abs(loss_x), f"loss {loss_k} vs XLA {loss_x}")
        check(rel <= GRAD_REL_L2, f"gradients differ from XLA by rel L2 {rel}")
        del grads_k, grads_x, ref

    del params
    manager, _ = make_manager(state, "smoke", lighthouse_addr, use_async_quorum=True)
    ftmesh.manager = manager
    losses: List[float] = []
    step_ms: List[float] = []
    try:
        for i in range(steps):
            t0 = time.perf_counter()
            manager.start_quorum()
            state["params"], state["opt"], loss, committed = step.ft_step(
                state["params"], state["opt"], batch
            )
            losses.append(float(np.asarray(loss)))  # host materialisation
            step_ms.append((time.perf_counter() - t0) * 1e3)
            check(committed, f"step {i} did not commit")
            if i == 0:
                out["overlap_commit_resolved"] = step.overlap_resolved
        out["steps_committed"] = manager.current_step()
        check(manager.current_step() == steps, "not every step committed")
    finally:
        manager.shutdown()
    check(all(np.isfinite(losses)), f"loss not finite: {losses}")
    if steps >= MIN_STEPS:
        check(losses[-1] < losses[0], f"loss did not fall: {losses[0]} -> {losses[-1]}")
    out["loss_first"], out["loss_last"] = losses[0], losses[-1]

    # The same gradient program timed with each completion barrier, one call
    # at a time (queued calls would each hold a gradient's worth of outputs,
    # which the 1B width has no room for); the median of three.
    def timed(barrier: Callable[[Any], Any]) -> float:
        ms = []
        for _ in range(3):
            t0 = time.perf_counter()
            res = step.grads(state["params"], batch)
            barrier(res)
            ms.append((time.perf_counter() - t0) * 1e3)
            del res
        return sorted(ms)[1]

    timed(jax.block_until_ready)  # warm: the call path compiles once
    bur_ms = timed(jax.block_until_ready)
    fetch_ms = timed(lambda res: float(np.asarray(res[0])))
    steady_ms = sorted(step_ms[1:])[len(step_ms[1:]) // 2] if len(step_ms) > 1 else step_ms[0]
    out.update(
        {
            "compile_seconds": round(compile_s, 2),
            "first_ft_step_ms": round(step_ms[0], 1),
            "smoke_ft_step_ms_median": round(steady_ms, 2),
            "smoke_tokens_per_s": round(model.batch_size * model.seq / steady_ms * 1e3, 1),
            "grads_ms_block_until_ready": round(bur_ms, 2),
            "grads_ms_host_fetch": round(fetch_ms, 2),
            "block_until_ready_waits": bool(bur_ms >= 0.5 * fetch_ms),
            "peak_bytes_in_use": peak_bytes(device),
            **cache_report(cache),
        }
    )
    if platform == "tpu":
        peak = bf16_peak(device.device_kind)
        out["smoke_ft_mfu"] = round(flops_per_step(model) / (steady_ms / 1e3) / peak, 4)
        # The gradient program holds every counted matmul; the update adds none.
        out["grads_mfu_block_until_ready"] = round(
            flops_per_step(model) / (bur_ms / 1e3) / peak, 4
        )
        check(
            out["smoke_ft_mfu"] <= 1.0 and out["grads_mfu_block_until_ready"] <= 1.0,
            "MFU above 100% of the device's bf16 peak (benchmark/peaks.json): "
            f"{out['smoke_ft_mfu']}, {out['grads_mfu_block_until_ready']}",
        )
    out["seconds"] = round(time.perf_counter() - t_phase, 1)
    return out


def _memory_analysis(compiled) -> Optional[Dict[str, int]]:
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    return {
        "argument": int(ma.argument_size_in_bytes),
        "output": int(ma.output_size_in_bytes),
        "temp": int(ma.temp_size_in_bytes),
    }


# ---------------------------------------------------------------------------
# heal: two replica groups as threads of one process on one chip.
# ---------------------------------------------------------------------------


class _InjectedFailure(Exception):
    pass


def heal_body(model: Model, platform: str) -> Dict[str, Any]:
    """Two replica groups as threads of ONE process on the one chip (the
    tests/harness.py Runner pattern): each a full replica with its own
    native Manager, a real TCPCollective ring over localhost and an
    HTTPTransport.  Merged steps, an injected failure in group 1, its
    restart from the seed, a live heal from the survivor, merged steps
    again.  The two share the chip's memory, so their device programs take
    turns (`chip`); the ring and the heal run concurrently as they must."""
    t_phase = time.perf_counter()
    device = require_platform(platform)
    import jax
    import numpy as np

    from torchft_tpu._native import LighthouseServer
    from torchft_tpu.ddp import GradientAverager

    cache = compile_counter()
    chip = threading.Lock()
    fail_after = 4  # group 1's first attempt dies once it has committed this many steps
    min_steps = fail_after + 1 + TAIL_MERGED
    first: Dict[int, Dict[str, Any]] = {}  # step-0 local and averaged gradient samples
    heals: List[Dict[str, Any]] = []
    t_first: List[float] = []  # the first gradient call, compile included
    # min_replicas=2: this deployment never steps with one group, so step 0
    # is a merged step on the seed's parameters and the survivor waits for
    # the restarted group instead of running ahead alone.
    lighthouse = LighthouseServer(bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=10000)

    def replica(gid: int, attempt: int) -> Dict[str, Any]:
        with chip:
            _, step, batch, state = replica_setup(model, device, batch_seed=1000 + gid)

        def on_heal(sd) -> None:
            leaves = jax.tree.leaves(sd)
            heals.append(
                {
                    "group": gid,
                    "leaves": len(leaves),
                    "all_jax_arrays_on_device": on_device(sd, device),
                    "bytes": int(sum(l.nbytes for l in leaves)),
                }
            )

        manager, transport = make_manager(
            state, str(gid), lighthouse.address(), use_async_quorum=True, on_heal=on_heal
        )
        averager = GradientAverager(manager)
        streak, participants = 0, 0
        try:
            while not (manager.current_step() >= min_steps and streak >= TAIL_MERGED):
                manager.start_quorum()
                with chip:
                    t0 = time.perf_counter()
                    loss, grads = step.grads(state["params"], batch)
                    jax.block_until_ready(grads)
                    if not t_first:
                        t_first.append(time.perf_counter() - t0)
                at_seed = attempt == 0 and manager.current_step() == 0
                if at_seed:
                    first.setdefault(gid, {})["local"] = grad_sample(grads)
                avg = averager.allreduce(grads)
                del grads
                if at_seed:
                    first[gid]["avg"] = grad_sample(avg)
                if manager.should_commit():
                    # A heal lands inside should_commit: the load callback has
                    # replaced state, and the averaged gradient applies to it.
                    # apply() donates, so a snapshot still being served by
                    # reference must land first.
                    transport.wait_snapshot()
                    with chip:
                        state["params"], state["opt"] = step.apply(
                            state["params"], state["opt"], avg
                        )
                        jax.block_until_ready(state["params"])
                    participants = manager.num_participants()
                    streak = streak + 1 if participants == 2 else 0
                    if at_seed:
                        check(participants == 2, "step 0 was not a merged step")
                del avg
                check(np.isfinite(float(loss)), f"group {gid} loss not finite")
                if gid == 1 and attempt == 0 and manager.current_step() >= fail_after:
                    raise _InjectedFailure(f"group 1 dies at step {manager.current_step()}")
            with chip:
                digest = params_digest(state["params"])
            return {
                "group": gid,
                "attempt": attempt,
                "step": manager.current_step(),
                "last_commit_participants": participants,
                "loss_last": float(loss),
                "digest": digest,
                "params_on_device": on_device(state["params"], device),
                "wire_dtype": manager.collective().wire_dtype,
            }
        finally:
            manager.shutdown()

    def run_group(gid: int) -> Dict[str, Any]:
        for attempt in range(2):
            try:
                return replica(gid, attempt)
            except _InjectedFailure:
                continue
        raise RuntimeError(f"group {gid} failed twice")

    from concurrent.futures import ThreadPoolExecutor

    try:
        with ThreadPoolExecutor(max_workers=2, thread_name_prefix="replica") as pool:
            futures = [pool.submit(run_group, g) for g in (0, 1)]
            results = [f.result(timeout=900) for f in futures]
    finally:
        lighthouse.shutdown()

    check(results[1]["attempt"] == 1, "the failure was not injected")
    check(len(heals) == 1 and heals[0]["group"] == 1, f"expected one heal into group 1: {heals}")
    check(heals[0]["all_jax_arrays_on_device"], "a healed leaf is not a jax.Array on the device")
    check(
        all(r["last_commit_participants"] == 2 for r in results),
        "the last committed step did not have 2 participants",
    )
    check(results[0]["step"] == results[1]["step"], "the groups ended on different steps")
    check(results[0]["digest"] == results[1]["digest"], "params_digest differs across groups")
    check(all(r["params_on_device"] for r in results), "final parameters are not on the device")
    wire = results[0]["wire_dtype"]
    worst = max(
        compare_to_mean(first[g]["avg"], [first[0]["local"], first[1]["local"]], wire)
        for g in (0, 1)
    )
    return {
        "device": device_report(),
        "steps": results[0]["step"],
        "failure_injected_after_step": fail_after,
        "heal": heals[0],
        "last_commit_participants": 2,
        "params_digest": results[0]["digest"],
        "digests_identical": True,
        "wire_dtype": wire,
        "avg_grad_vs_f32_mean_max_rel": worst,
        "avg_grad_tolerance": WIRE_TOL[wire],
        "device_wire_prep": os.environ.get("TPUFT_DEVICE_WIRE_PREP", "(default)"),
        "first_grads_seconds_incl_compile": round(t_first[0], 2),
        "peak_bytes_in_use": peak_bytes(device),
        **cache_report(cache),
        "seconds": round(time.perf_counter() - t_phase, 1),
    }


# ---------------------------------------------------------------------------
# replicas (four chips): one process per chip under the Launcher.
# ---------------------------------------------------------------------------


def replica_worker(model: Model, platform: str, out_dir: str, min_steps: int) -> Dict[str, Any]:
    """The user's train loop of one replica group under the Launcher: owns
    exactly one device, `ft_step`s with a sync quorum (a healed group then
    starts its step on good weights), logs every step for the supervisor,
    and stops at the first step >= `min_steps` whose last TAIL_MERGED commits
    were merged — the same step on every group."""
    device = require_platform(platform)
    import jax
    import numpy as np

    from torchft_tpu.ddp import GradientAverager

    gid = int(os.environ["REPLICA_GROUP_ID"])
    num_groups = int(os.environ["NUM_REPLICA_GROUPS"])
    check(len(jax.devices()) == 1, f"group {gid} sees {len(jax.devices())} devices, not 1")
    cache = compile_counter()
    ftmesh, step, batch, state = replica_setup(model, device, batch_seed=1000 + gid)
    heals: List[bool] = []  # per applied heal: every leaf a jax.Array on the device
    manager, _ = make_manager(
        state,
        str(gid),
        None,  # TPUFT_LIGHTHOUSE, from the launcher
        use_async_quorum=False,
        on_heal=lambda sd: heals.append(on_device(sd, device)),
    )
    ftmesh.manager = manager
    log_path = os.path.join(out_dir, f"g{gid}.steps.jsonl")
    ready = os.path.join(out_dir, f"g{gid}.ready")
    first_incarnation = not os.path.exists(ready)
    t0 = time.perf_counter()
    jax.block_until_ready(step.grads(state["params"], batch))  # compile before the quorum
    compile_s = time.perf_counter() - t0
    if first_incarnation:
        # Every group heartbeats and has compiled before any asks for a
        # quorum, so step 0 is a merged step on the seed's parameters.
        open(ready, "w").close()
        deadline = time.monotonic() + 900
        while not all(
            os.path.exists(os.path.join(out_dir, f"g{g}.ready")) for g in range(num_groups)
        ):
            check(time.monotonic() < deadline, "the other groups never became ready")
            time.sleep(0.05)
        time.sleep(1.0)  # ten heartbeat intervals: the lighthouse knows every group
    streak, participants, loss = 0, 0, float("nan")
    try:
        while not (manager.current_step() >= min_steps and streak >= TAIL_MERGED):
            manager.start_quorum()  # sync: a heal has landed in `state` by now
            at_seed = first_incarnation and manager.current_step() == 0
            if at_seed and num_groups > 1:
                # Once, in the split form ft_step wraps, to see the averaged
                # gradient the single-process reference is compared with.
                loss, grads = step.grads(state["params"], batch)
                avg = GradientAverager(manager).allreduce(grads)
                np.savez(os.path.join(out_dir, f"g{gid}.avg_grad_sample.npz"), **grad_sample(avg))
                committed = manager.should_commit()
                check(committed and manager.num_participants() == num_groups,
                      "step 0 was not a merged step")
                state["params"], state["opt"] = step.apply(state["params"], state["opt"], avg)
                del grads, avg
            else:
                state["params"], state["opt"], loss, committed = step.ft_step(
                    state["params"], state["opt"], batch
                )
            loss = float(np.asarray(loss))
            check(np.isfinite(loss), f"group {gid} loss not finite")
            if committed:
                participants = manager.num_participants()
                streak = streak + 1 if participants == num_groups else 0
            with open(log_path, "a", encoding="utf-8") as f:
                f.write(json.dumps({
                    "pid": os.getpid(), "step": manager.current_step(), "committed": committed,
                    "participants": participants, "streak": streak, "loss": loss,
                }) + "\n")
        return {
            "group": gid,
            "pid": os.getpid(),
            "first_incarnation": first_incarnation,
            "device": device_report(),
            "chip_files": _chip_files(),
            "step": manager.current_step(),
            "last_commit_participants": participants,
            "heals_applied": len(heals),
            "healed_onto_device": all(heals),
            "digest": params_digest(state["params"]),
            "wire_dtype": manager.collective().wire_dtype,
            "compile_seconds": round(compile_s, 2),
            "peak_bytes_in_use": peak_bytes(device),
            **cache_report(cache),
        }
    finally:
        manager.shutdown()


def _chip_files() -> List[str]:
    """Device nodes this process holds open — what tells one chip from
    another when every one-chip process numbers its device 0."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            link = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        # /dev/vfio/vfio is the container every process opens, not a chip.
        if link.startswith(("/dev/accel", "/dev/vfio/")) and link != "/dev/vfio/vfio":
            held.add(link)
    return sorted(held)


def replicas_reference(model: Model, platform: str, out_dir: str, num_groups: int) -> Dict[str, Any]:
    """What `replicas` is compared with: a single process computes the same
    `num_groups` seeded per-group gradients on the seed's parameters and
    averages them in float32; every group's recorded step-0 average must
    match within the wire dtype's tolerance."""
    t_phase = time.perf_counter()
    device = require_platform(platform)
    import jax
    import numpy as np

    _, step, _, state = replica_setup(model, device, batch_seed=0)
    locals_ = [
        grad_sample(step.grads(state["params"], seeded_batch(model, seed=1000 + g))[1])
        for g in range(num_groups)
    ]
    results = [
        json.load(open(os.path.join(out_dir, f"g{g}.result.json"), encoding="utf-8"))
        for g in range(num_groups)
    ]
    worst = 0.0
    for g in range(num_groups):
        with np.load(os.path.join(out_dir, f"g{g}.avg_grad_sample.npz")) as avg:
            worst = max(worst, compare_to_mean(dict(avg), locals_, results[g]["wire_dtype"]))
    return {
        "device": device_report(),
        "avg_grad_vs_f32_mean_max_rel": worst,
        "avg_grad_tolerance": WIRE_TOL[results[0]["wire_dtype"]],
        "seconds": round(time.perf_counter() - t_phase, 1),
    }


# ---------------------------------------------------------------------------
# mesh (four chips): one process, one group on an fsdp x tensor mesh.
# ---------------------------------------------------------------------------


def mesh_body(model: Model, platform: str, *, steps: int = 3) -> Dict[str, Any]:
    """One process, four devices, one replica group on an in-group
    `fsdp=2 x tensor=2` mesh: every parameter sharded over four distinct
    devices, `steps` `ft_step`s, and the first loss compared with the loss
    of the same parameters and batch on one device."""
    t_phase = time.perf_counter()
    device = require_platform(platform)
    import jax
    import numpy as np

    from torchft_tpu._native import LighthouseServer
    from torchft_tpu.models import init_params, loss_fn
    from torchft_tpu.models.transformer import param_axes
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    check(len(jax.devices()) >= 4, f"the mesh phase needs 4 devices, JAX has {len(jax.devices())}")
    cache = compile_counter()
    cfg = model.cfg
    devices = jax.devices()[:4]
    host_params = jax.device_get(init_params(jax.random.PRNGKey(0), cfg))

    # What it is compared with: a one-device program on the same host.  Its
    # gate must not care that the host has four chips.
    one = ft_init_mesh({"data": 1}, devices=[device])
    one_step = TrainStep(one, model.tx(), lambda p, b: loss_fn(p, b, cfg))
    one_params = jax.device_put(host_params, device)
    one_batch = seeded_batch(model, seed=0)
    one_found = kernels_in(one_step.lower_grads(one_params, one_batch).compile().as_text())
    loss_one = float(one_step.grads(one_params, one_batch)[0])
    del one_params

    ftmesh = ft_init_mesh({"fsdp": 2, "tensor": 2}, devices=devices)
    step = TrainStep(
        ftmesh, model.tx(), lambda p, b: loss_fn(p, b, cfg, ftmesh.mesh, ftmesh.rules)
    )
    params = ftmesh.shard_params(host_params, param_axes(cfg))
    del host_params
    layout = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        shards = leaf.addressable_shards
        want = leaf.sharding.shard_shape(leaf.shape)
        name = jax.tree_util.keystr(path)
        check(len({s.device for s in shards}) == 4, f"{name} is not on four distinct devices")
        check(all(s.data.shape == want for s in shards), f"{name} shard shapes are not {want}")
        check(np.prod(want) < np.prod(leaf.shape), f"{name} sits whole on a device")
        layout.append({"leaf": name, "shape": list(leaf.shape), "shard": list(want),
                       "spec": str(leaf.sharding.spec)})
    batch = seeded_batch(model, seed=0, sharding=ftmesh.sharding("batch", "seq"))

    t0 = time.perf_counter()
    compiled = step.lower_grads(params, batch).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    found = kernels_in(text)
    out: Dict[str, Any] = {
        "device": device_report(),
        "mesh": dict(ftmesh.mesh.shape),
        "kernels_in_mesh_program": found,
        "kernels_in_one_device_program_on_this_host": one_found,
        "collectives_in_mesh_program": {
            op: text.count(f" {op}(") + text.count(f" {op}-start(")
            for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")
        },
        "mesh_program_bytes_per_device": _memory_analysis(compiled),
    }
    del compiled, text
    # The decision this PR takes: under a mesh the gate turns the kernels
    # off (says so once at trace time) and XLA shards its own formulation.
    check(not any(found.values()), f"a pallas call is in the sharded program: {found}")
    if platform == "tpu":
        check(all(one_found.values()),
              f"the one-device program lost a kernel on a multi-chip host: {one_found}")

    state = {"params": params, "opt": step.init_opt_state(params)}
    del params
    lighthouse = LighthouseServer(bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=100)
    manager, _ = make_manager(state, "mesh", lighthouse.address(), use_async_quorum=True)
    ftmesh.manager = manager
    losses: List[float] = []
    try:
        for i in range(steps):
            manager.start_quorum()
            state["params"], state["opt"], loss, committed = step.ft_step(
                state["params"], state["opt"], batch
            )
            losses.append(float(np.asarray(loss)))
            check(committed, f"mesh step {i} did not commit")
        # Which way the averager's device fetch went for the sharded
        # gradients: slices > 0 is the per-shard fetch (`_shard_slices`
        # recognised the layout), 0 one full-width fetch per bucket — and no
        # statistics at all means neither: a lone participating group's
        # allreduce returns before any fetch (ddp.py, "size() == 1").
        stats = dict(step._averager.last_stats)
        out["averager_fetch"] = (
            "none: one participating group, the averager returns before any device fetch"
            if not stats
            else f"{stats['slices']} shard slices" if stats["slices"]
            else f"one full-width fetch per bucket ({stats['buckets']} buckets)"
        )
    finally:
        manager.shutdown()
        lighthouse.shutdown()
    check(all(np.isfinite(losses)), f"mesh loss not finite: {losses}")
    check(
        abs(losses[0] - loss_one) <= MESH_LOSS_RTOL * abs(loss_one),
        f"mesh loss {losses[0]} vs one-device loss {loss_one}",
    )
    for leaf in jax.tree.leaves(state["params"]):
        check(len({s.device for s in leaf.addressable_shards}) == 4,
              "a parameter left the mesh during training")
    out.update(
        {
            "layout": layout,
            "loss_mesh_first": losses[0],
            "loss_one_device": loss_one,
            "loss_rtol": MESH_LOSS_RTOL,
            "losses": losses,
            "compile_seconds": round(compile_s, 2),
            "peak_bytes_in_use": [peak_bytes(d) for d in devices],
            **cache_report(cache),
            "seconds": round(time.perf_counter() - t_phase, 1),
        }
    )
    return out


# ---------------------------------------------------------------------------
# Parent: orchestration only, never JAX.
# ---------------------------------------------------------------------------


class PhaseFailed(RuntimeError):
    pass


def _tail(path: str, n: int = 6000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return "(no log)"


def _child(name: str, out_dir: str, argv: List[str], timeout: float,
           env: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Runs `chip_smoke.py <argv>` as a child that owns the chip alone and
    returns the JSON object on its last stdout line."""
    log = os.path.join(out_dir, f"{name}.log")
    with open(log, "ab") as err:
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), *argv],
                stdout=subprocess.PIPE, stderr=err, timeout=timeout, cwd=REPO,
                env=dict(os.environ, **(env or {})),
            )
        except subprocess.TimeoutExpired as e:
            raise PhaseFailed(f"{name}: no end within {timeout:.0f} s\n{_tail(log)}") from e
    if proc.returncode != 0:
        raise PhaseFailed(f"{name}: exit code {proc.returncode}\n{_tail(log)}")
    return json.loads(proc.stdout.decode().strip().splitlines()[-1])


def _supervise(launcher, out_dir: str, num_groups: int, timeout: float,
               on_tick: Callable[[], None] = lambda: None) -> List[Dict[str, Any]]:
    """Supervises the launcher's groups until each has written its result."""
    deadline = time.monotonic() + timeout
    paths = [os.path.join(out_dir, f"g{g}.result.json") for g in range(num_groups)]
    while not (all(os.path.exists(p) for p in paths) and not launcher.running()):
        if time.monotonic() > deadline:
            raise PhaseFailed(f"workers: no end within {timeout:.0f} s\n"
                              + "\n".join(_tail(os.path.join(out_dir, f"g{g}.log"), 3000)
                                          for g in range(num_groups)))
        launcher.supervise_once()
        if launcher.exhausted():
            g = launcher.exhausted()[0]
            raise PhaseFailed(f"group {g} failed\n{_tail(os.path.join(out_dir, f'g{g}.log'))}")
        on_tick()
        time.sleep(0.05)
    launcher.supervise_once()  # notes the last exit codes
    check(launcher.all_exited_clean(), "a worker did not exit cleanly")
    return [json.load(open(p, encoding="utf-8")) for p in paths]


def phase_train(out_dir: str) -> Dict[str, Any]:
    from torchft_tpu.launch import Launcher

    cmd = [sys.executable, os.path.abspath(__file__), "--worker", "train", "--out", out_dir]
    with Launcher(cmd, num_groups=1, lighthouse="embed", min_replicas=1,
                  join_timeout_ms=100, max_restarts=0, log_dir=out_dir, cwd=REPO) as launcher:
        return _supervise(launcher, out_dir, 1, timeout=900)[0]


def chip_env(group: int) -> Dict[str, str]:
    """The TPU runtime's per-process visibility settings that confine one
    child to chip `group` of the host: one process per chip."""
    return {
        "TPU_VISIBLE_CHIPS": str(group),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def _read_steps(out_dir: str, group: int) -> List[Dict[str, Any]]:
    try:
        with open(os.path.join(out_dir, f"g{group}.steps.jsonl"), encoding="utf-8") as f:
            return [json.loads(l) for l in f if l.endswith("\n")]
    except OSError:
        return []


def phase_replicas(
    out_dir: str,
    cmd: List[str],
    *,
    num_groups: int = 4,
    victim: int = 1,
    group_env: Optional[Dict[int, Dict[str, str]]] = None,
) -> Dict[str, Any]:
    """`num_groups` replica groups through the Launcher, each running `cmd`
    (a `replica_worker`); with `group_env`, each confined to its own chip.
    SIGKILLs `victim` after KILL_AFTER merged steps; the launcher restarts
    it, it heals live, and every group ends on the same merged step."""
    import signal

    from torchft_tpu.launch import Launcher

    t_phase = time.perf_counter()
    killed: Dict[str, Any] = {}

    # min_replicas = all but one: step 0 cannot form without (nearly) every
    # group, and the survivors of one kill still make a quorum.
    with Launcher(cmd, num_groups=num_groups, lighthouse="embed", min_replicas=num_groups - 1,
                  join_timeout_ms=10000, max_restarts=3, log_dir=out_dir, cwd=REPO,
                  group_env=group_env) as launcher:

        def maybe_kill() -> None:
            if killed:
                return
            steps = _read_steps(out_dir, victim)
            merged = [s for s in steps if s["committed"] and s["participants"] == num_groups]
            if len(merged) >= KILL_AFTER:
                killed.update(pid=launcher.pid(victim), at_step=steps[-1]["step"])
                launcher.kill(victim, sig=signal.SIGKILL, hold=False)

        results = _supervise(launcher, out_dir, num_groups, timeout=1500, on_tick=maybe_kill)
        restarts = launcher.restarts(victim)

    check(bool(killed), "the victim was never killed")
    check(killed["at_step"] < MIN_STEPS_REPLICAS, "the kill came after the step budget")
    check(restarts >= 1, "the launcher never restarted the victim")
    check(all(r["device"]["count"] == 1 and r["device"]["platform"] == results[0]["device"]["platform"]
              for r in results), "a worker did not see exactly one device")
    chips = [tuple(r["chip_files"]) for r in results]
    if group_env is not None:
        check(all(chips) and len(set(chips)) == num_groups,
              f"the workers do not hold {num_groups} distinct chips: {chips}")
    check(results[victim]["pid"] != killed["pid"] and not results[victim]["first_incarnation"],
          "the victim's result is not from its restarted incarnation")
    check(results[victim]["heals_applied"] >= 1, "no heal was applied in the restarted group")
    check(results[victim]["healed_onto_device"], "a healed leaf is not a jax.Array on the device")
    check(all(r["last_commit_participants"] == num_groups for r in results),
          "the last committed step was not merged")
    check(len({r["step"] for r in results}) == 1, "the groups ended on different steps")
    check(len({r["digest"] for r in results}) == 1, "params_digest differs across groups")
    post = [s for s in _read_steps(out_dir, victim)
            if s["pid"] == results[victim]["pid"] and s["committed"]
            and s["participants"] == num_groups]
    check(len(post) >= TAIL_MERGED, f"only {len(post)} merged steps after the heal")
    return {
        "device": dict(results[0]["device"], count=num_groups),
        "groups": num_groups,
        "chips": [list(c) for c in chips],
        "killed": killed,
        "victim_restarts": restarts,
        "merged_steps_after_heal": len(post),
        "final_step": results[0]["step"],
        "params_digest": results[0]["digest"],
        "digests_identical": True,
        "workers": results,
        "seconds": round(time.perf_counter() - t_phase, 1),
    }


def run_phases(phases: Sequence[Tuple[str, Callable[[], Dict[str, Any]]]]) -> int:
    """Runs the phases in order, printing one JSON line each (`"passed"`,
    never `"ok"`: that key is the last line's alone) and the contract's line
    last.  A phase that raises ends the run: exit code 1 and no
    `"ok": true`."""
    t0 = time.perf_counter()
    devices = []
    for name, run in phases:
        try:
            result = run()
        except Exception as e:  # noqa: BLE001 — the boundary: report, then fail
            print(json.dumps({"phase": name, "passed": False, "error": f"{type(e).__name__}: {e}"[:4000]}))
            print(f"chip_smoke: phase {name} failed after {time.perf_counter() - t0:.0f} s",
                  file=sys.stderr)
            return 1
        print(json.dumps({"phase": name, "passed": True, **result}), flush=True)
        devices.append(result["device"])
    if jax_backend_created():
        print("chip_smoke: the parent initialised a JAX backend", file=sys.stderr)
        return 1
    if any(d != devices[0] for d in devices):
        print(f"chip_smoke: the phases reported different devices: {devices}", file=sys.stderr)
        return 1
    print(json.dumps({"total_seconds": round(time.perf_counter() - t0, 1)}))
    print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


def parent(four_chips: bool) -> int:
    from torchft_tpu.launch import export_compile_cache

    export_compile_cache()  # children inherit the one location
    import torchft_tpu._native  # noqa: F401 — built/loaded once, before any child

    out_dir = os.path.join(OUT_ROOT, time.strftime("%Y%m%dT%H%M%S") + f"_{os.getpid()}")
    os.makedirs(out_dir)
    if four_chips:
        def replicas() -> Dict[str, Any]:
            result = phase_replicas(
                out_dir,
                [sys.executable, os.path.abspath(__file__), "--worker", "replicas",
                 "--out", out_dir],
                group_env={g: chip_env(g) for g in range(4)},
            )
            result["reference"] = _child(
                "replicas_reference", out_dir,
                ["--phase", "replicas_reference", "--out", out_dir], timeout=600,
                env=chip_env(0),
            )
            return result

        phases = [
            ("replicas", replicas),
            ("mesh", lambda: _child("mesh", out_dir, ["--phase", "mesh"], timeout=900)),
        ]
    else:
        phases = [
            ("train", lambda: phase_train(out_dir)),
            ("heal", lambda: _child("heal", out_dir, ["--phase", "heal"], timeout=900)),
            ("large", lambda: _child("large", out_dir, ["--phase", "large"], timeout=900)),
        ]
    return run_phases(phases)


def _write_result(out_dir: str, result: Dict[str, Any]) -> None:
    gid = os.environ["REPLICA_GROUP_ID"]
    tmp = os.path.join(out_dir, f"g{gid}.result.json.{os.getpid()}")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(result, f)
    os.replace(tmp, os.path.join(out_dir, f"g{gid}.result.json"))


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run the replicas and mesh phases (needs four chips)")
    # The two below are how the script starts its own children.
    parser.add_argument("--worker", choices=("train", "replicas"), help=argparse.SUPPRESS)
    parser.add_argument("--phase", choices=("heal", "large", "mesh", "replicas_reference"),
                        help=argparse.SUPPRESS)
    parser.add_argument("--out", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.worker == "train":
        _write_result(args.out, train_body(
            flagship(), "tpu", None, steps=MIN_STEPS, reference=True))
    elif args.worker == "replicas":
        _write_result(args.out, replica_worker(flagship(), "tpu", args.out, MIN_STEPS_REPLICAS))
    elif args.phase == "heal":
        print(json.dumps(heal_body(flagship(), "tpu")))
    elif args.phase == "large":
        from torchft_tpu._native import LighthouseServer

        lighthouse = LighthouseServer(bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=100)
        try:
            print(json.dumps(train_body(
                large(), "tpu", lighthouse.address(), steps=3, reference=False)))
        finally:
            lighthouse.shutdown()
    elif args.phase == "mesh":
        print(json.dumps(mesh_body(flagship(), "tpu")))
    elif args.phase == "replicas_reference":
        print(json.dumps(replicas_reference(flagship(), "tpu", args.out, 4)))
    else:
        return parent(args.four_chips)
    return 0


if __name__ == "__main__":
    sys.exit(main())
