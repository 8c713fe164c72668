"""How a looped language model (Ouro, `model_type: ouro`: a stack of dense
blocks that runs `total_ut_steps` times over the same weights, four norms a
block, a head and an exit gate after every pass) is handed to the program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig`: `loop_steps` passes, a `pattern` of one
`LayerKind` with `post_norms`, `exit_beta` the exit-weighted loss's entropy
coefficient) and builds the system under test through the library's entry
points.  The optimizer, the Manager and the averager are the dense
configurations' (`programs/dense_lm.py`, beside this file).  It raises on every
key it does not honour.  Nothing here computes a result that is compared.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict

from benchmark import spec

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DENSE = spec._module("programs", "dense_lm", _BENCH_DIR)
optimizer = _DENSE.optimizer
manager = _DENSE.manager
gradient_averager = _DENSE.gradient_averager
kernel_names = _DENSE.kernel_names  # `attn`: tpuft_fa_*, `ce`: tpuft_ce_* — the kernels a dense model runs


def transformer_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from torchft_tpu.models import LayerKind, TransformerConfig

    if config.get("use_sliding_window") or config.get("sliding_window") is not None:
        raise ValueError("this file hands over attention over all of the past")
    if config.get("rope_scaling") is not None:
        raise ValueError("this file hands over plain RoPE: no scaling")
    if config["tie_word_embeddings"]:
        raise ValueError("this file hands over an untied head")
    if config["early_exit_threshold"] != 1:
        raise ValueError("a training step runs every pass: an exit threshold under 1 is inference's")
    if config["hidden_act"] != "silu":
        raise ValueError("this file hands over SiLU-gated feed-forwards")
    layers = config["num_hidden_layers"]
    if any(kind != "full_attention" for kind in config["layer_types"][:layers]):
        raise ValueError("every layer attends over all of the past")
    if config["total_ut_steps"] < 2:
        raise ValueError("a looped model runs its layers twice at least; one pass is a dense_lm configuration")
    training, program = config["training"], config["program"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    kind = LayerKind("layers", False, config["num_attention_heads"], float(config["rope_theta"]), post_norms=True)
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=layers,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        dtype=dtypes[training["compute_dtype"]],
        param_dtype=dtypes[training["param_dtype"]],
        remat=program["remat"],
        remat_keeps_attention=bool(program.get("remat_keeps_attention", False)),
        scan_unroll=program["scan_unroll"],
        pattern=(kind,) * layers,
        loop_steps=config["total_ut_steps"],
        loop_scan=bool(program["loop_scan"]),
        exit_beta=float(config["exit_loss"]["beta"]),
    )


def loss(config: Dict[str, Any]):
    """(params, batch) -> (loss, counters) as the train step takes it."""
    from torchft_tpu.models.transformer import loss_and_counters

    cfg = transformer_config(config)
    return lambda p, b: loss_and_counters(p, b, cfg)


def train_step(config: Dict[str, Any], device):
    """(ftmesh, TrainStep) of one replica group on one device.  The loss hands
    out the model's counters (the exit distribution's mass a pass, the passes'
    mean losses, the exit distribution's entropy), which `ft_step` lands in the
    program's `step_summary` records."""
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), loss(config), loss_has_counters=True)
