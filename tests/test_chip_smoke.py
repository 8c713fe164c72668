"""Rehearses `chip_smoke.py` without the chip: its phase bodies are
functions of the model and the required platform, so they run here
in-process at a tiny width on the virtual CPU devices.  The steering is in
this test; the script has no option or variable for it.  Also pins the
script's contract off the chip (non-zero, no result) and that nothing the
parent imports creates a JAX backend.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    import chip_smoke
    from torchft_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=128, max_seq=32
    )
    return chip_smoke.Model(cfg, batch_size=4, seq=32, optimizer="adamw")


def test_train_phase_rehearsal(tiny) -> None:
    import chip_smoke
    from torchft_tpu._native import LighthouseServer

    lighthouse = LighthouseServer(bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=100)
    try:
        out = chip_smoke.train_body(
            tiny, "cpu", lighthouse.address(), steps=chip_smoke.MIN_STEPS, reference=True
        )
    finally:
        lighthouse.shutdown()
    assert out["steps_committed"] == chip_smoke.MIN_STEPS
    assert out["loss_last"] < out["loss_first"]
    assert out["device"]["platform"] == "cpu"
    # Off the chip the kernels are off and both formulations are the same program.
    assert not any(out["kernels_in_gradient_program"].values())
    assert out["first_step_vs_xla"]["grad_rel_l2"] == 0.0
    assert out["overlap_commit_resolved"] is True  # no memory statistics on the CPU


def test_heal_phase_rehearsal(tiny) -> None:
    import chip_smoke

    out = chip_smoke.heal_body(tiny, "cpu")
    assert out["heal"]["group"] == 1 and out["heal"]["all_jax_arrays_on_device"]
    assert out["digests_identical"] and out["last_commit_participants"] == 2
    assert out["steps"] >= out["failure_injected_after_step"] + 1 + chip_smoke.TAIL_MERGED
    assert out["avg_grad_vs_f32_mean_max_rel"] <= out["avg_grad_tolerance"]


def test_mesh_phase_rehearsal(tiny) -> None:
    import chip_smoke

    out = chip_smoke.mesh_body(tiny, "cpu")
    assert out["mesh"] == {"fsdp": 2, "tensor": 2}
    assert not any(out["kernels_in_mesh_program"].values())
    assert all(
        sum(layer["shard"]) < sum(layer["shape"]) for layer in out["layout"]
    ), "a parameter sits whole on one device"
    assert out["collectives_in_mesh_program"]["all-reduce"] > 0
    assert abs(out["loss_mesh_first"] - out["loss_one_device"]) <= 1e-3


def test_wrong_platform_is_refused(tiny) -> None:
    import chip_smoke

    with pytest.raises(RuntimeError, match="needs platform 'tpu'"):
        chip_smoke.mesh_body(tiny, "tpu")


def test_script_fails_fast_without_a_tpu() -> None:
    """`python chip_smoke.py` where JAX finds no accelerator: non-zero, soon,
    and no `"ok": true` anywhere in what it prints."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs platform 'tpu'" in out.stdout  # the worker's refusal, relayed


def test_a_phase_that_raises_fails_the_run(capsys, monkeypatch) -> None:
    import chip_smoke

    def boom():
        raise RuntimeError("phase broke")

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    rc = chip_smoke.run_phases([("fine", lambda: {"device": device}), ("boom", boom),
                                ("never", lambda: pytest.fail("ran after a failure"))])
    printed = capsys.readouterr().out
    assert rc != 0
    assert '"ok"' not in printed
    assert json.loads(printed.strip().splitlines()[-1])["passed"] is False
    # A parent that initialised a backend (this test process has) fails too.
    monkeypatch.setattr(chip_smoke, "jax_backend_created", lambda: True)
    assert chip_smoke.run_phases([("fine", lambda: {"device": device})]) != 0
    assert '"ok"' not in capsys.readouterr().out
    # Without either, the last line is the contract's, exactly.
    monkeypatch.setattr(chip_smoke, "jax_backend_created", lambda: False)
    assert chip_smoke.run_phases([("fine", lambda: {"device": device})]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": device}


def test_an_mfu_is_never_taken_against_a_guessed_peak() -> None:
    """The smoke's MFU line divides by the benchmark's table
    (`benchmark/peaks.json`), and a device kind the table does not hold is an
    error; the operation count is the benchmark's too (the embedding table
    is a gather and counts nothing)."""
    import chip_smoke

    assert chip_smoke.bf16_peak("TPU v5 lite") == 197e12
    with pytest.raises(RuntimeError, match="no peaks recorded"):
        chip_smoke.bf16_peak("TPU v99 imaginary")

    flagship = chip_smoke.flagship()
    cfg, tokens = flagship.cfg, flagship.batch_size * flagship.seq
    matmul = cfg.n_layers * (4 * cfg.d_model**2 + 3 * cfg.d_model * cfg.d_ff) + cfg.d_model * cfg.vocab_size
    attention = cfg.n_layers * 6 * cfg.d_model * (flagship.seq + 1)
    assert chip_smoke.flops_per_step(flagship) == (6 * matmul + attention) * tokens


@pytest.mark.parametrize("module", ["torchft_tpu", "torchft_tpu.launch", "chip_smoke"])
def test_import_creates_no_jax_backend(module) -> None:
    """A chip belongs to one process: what a parent imports before it starts
    children must not take it.  Checked in a fresh interpreter."""
    code = (
        f"import {module}, chip_smoke, sys\n"
        "import jax  # the check itself needs jax imported, not initialised\n"
        "sys.exit(1 if chip_smoke.jax_backend_created() else 0)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120, cwd=REPO
    )
    assert out.returncode == 0, out.stderr[-2000:]
