"""The plain reference against the program at a tiny size, on the CPU.

In float32 the two agree to rounding: the mathematics is the same.  With the
compute type the configurations state (bf16) the program stays inside the
limit, and the control — the reference computed in the next precision down,
fp8 operands — does not: the comparison can fail.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.spec import Benchmark

BENCH = Benchmark()
REFERENCE = BENCH.reference("dense_lm")
PROGRAM = BENCH.program("dense_lm")
SEEDS = (3, 2**31 + 5, 77)


def tiny(compute: str):
    return dict(
        architecture="dense_lm", vocab_size=512, hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=512, max_position_embeddings=256, rope_theta=1e6,
        rms_norm_eps=1e-5,
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
        program=dict(remat=False, scan_unroll=2),
        # float32: rounding and the program's fixed epsilon; bfloat16: as the configurations' files
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.03),
    )


def one_step(config, seed):
    from torchft_tpu.models import loss_fn

    cfg = PROGRAM.transformer_config(config)
    weights = REFERENCE.make_weights(seed, config)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, config["vocab_size"], size=(2, 128)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(np.roll(tokens, -1, axis=1))}
    loss, grads = jax.jit(jax.value_and_grad(lambda p, b: loss_fn(p, b, cfg)))(weights, batch)
    return weights, batch, loss, grads


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_program_agrees_with_the_reference(seed):
    config = tiny("float32")
    weights, batch, loss, grads = one_step(config, seed)
    indices = compare.sample_indices(seed, weights)
    out = compare.against_reference(REFERENCE, config, weights, batch, loss, compare.sample(grads, indices), indices)
    assert out["ok"], out
    assert out["loss_rel"] < 1e-5 and out["grad_rel"] < 1e-4


@pytest.mark.parametrize("seed", SEEDS)
def test_bf16_program_passes_and_the_fp8_control_fails(seed):
    config = tiny("bfloat16")
    weights, batch, loss, grads = one_step(config, seed)
    indices = compare.sample_indices(seed, weights)
    sound = compare.against_reference(REFERENCE, config, weights, batch, loss, compare.sample(grads, indices), indices)
    assert sound["ok"], sound
    closs, cgrads = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], config, "float8")
    control = compare.against_reference(REFERENCE, config, weights, batch, closs, compare.sample(cgrads, indices), indices)
    assert not control["ok"], control
    assert control["grad_rel"] > 3 * sound["grad_rel"]


def test_bf16_parameters_fail_the_float32_comparison():
    """Weights rounded to bf16 are another model: the float32 comparison sees it."""
    config = tiny("float32")
    weights, batch, _, _ = one_step(config, 3)
    rounded = jax.tree.map(lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), weights)
    loss, grads = REFERENCE.loss_and_grads(rounded, batch["tokens"], batch["targets"], config)
    indices = compare.sample_indices(3, weights)
    out = compare.against_reference(REFERENCE, config, weights, batch, loss, compare.sample(grads, indices), indices)
    assert not out["ok"], out


def test_weights_come_from_the_seed_alone():
    config = tiny("float32")
    a, b, c = (REFERENCE.make_weights(s, config) for s in (5, 5, 6))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["lm_head"], c["lm_head"]))
    big = REFERENCE.make_weights(2**31 + 9, config)  # the driver's seeds pass 32 signed bits
    assert bool(jnp.all(jnp.isfinite(big["embed"])))


def test_mean_of_locals_catches_a_bf16_wire():
    rng = np.random.default_rng(0)
    locals_ = [{"w": rng.standard_normal(1000).astype(np.float32)} for _ in range(4)]
    mean = np.mean(np.stack([l["w"] for l in locals_]), axis=0)
    assert compare.mean_of_locals({"w": mean}, locals_, 1e-5)["ok"]
    import ml_dtypes

    lossy = np.mean(np.stack([l["w"].astype(ml_dtypes.bfloat16).astype(np.float32) for l in locals_]), axis=0)
    assert not compare.mean_of_locals({"w": lossy}, locals_, 1e-5)["ok"]
