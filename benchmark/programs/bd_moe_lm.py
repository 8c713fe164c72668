"""How a sparse-expert language model trained by block diffusion
(SDAR-30B-A3B-Chat, `model_type: sdar_moe`: Qwen3-MoE's decoder under BD3-LM's
objective) is handed to the program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig`: grouped-query attention at an explicit
head width with a per-head QK-norm, the softmax router with renormalised
gates, WHICH of the router's experts this chip holds, and — the one setting of
the objective — `bd_block_length`, with the noise's seed) and builds the system
under test through the library's entry points.  The optimizer, the Manager and
the averager are the dense configurations' (`programs/dense_lm.py`, beside this
file).  It raises on every key it does not honour.  Nothing here computes a
result that is compared.
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

# What the objective's group may hold: the block length and the noise's seed are handed
# over; the rest is the program's own and stated so that a file cannot ask for another objective in silence.
_OBJECTIVE = {"schedule": "linear", "loss_weight": "1/t", "shift": False, "mask_token": "last_row_of_the_slice"}


def transformer_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from torchft_tpu.models import TransformerConfig

    if config.get("mlp_only_layers") or config.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer's feed-forward is the sparse one")
    if config.get("use_sliding_window") or config.get("sliding_window") is not None:
        raise ValueError("no sliding window here")
    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        raise ValueError("no attention bias and an untied head")
    if config["hidden_act"] != "silu" or config.get("rope_scaling") is not None:
        raise ValueError("SwiGLU experts and unscaled RoPE")
    if not config["norm_topk_prob"]:
        raise ValueError("the chosen gates are renormalised")
    diffusion = dict(config["block_diffusion"])
    block_length, noise_seed = int(diffusion.pop("block_length")), int(diffusion.pop("noise_seed"))
    if diffusion != _OBJECTIVE:
        raise ValueError(f"the program's block-diffusion objective is {_OBJECTIVE}, not {diffusion}")
    training, program = config["training"], config["program"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    share = config.get("expert_parallel") or {}
    routed = share.get("router_outputs", config["num_experts"])
    held = (share.get("first_expert_held", 0), config["num_experts"])
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        qk_norm_per_head=True,
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        dtype=dtypes[training["compute_dtype"]],
        param_dtype=dtypes[training["param_dtype"]],
        remat=program["remat"],
        remat_keeps_attention=bool(program.get("remat_keeps_attention", False)),
        scan_unroll=program["scan_unroll"],
        rms_eps=float(config["rms_norm_eps"]),
        moe_experts=routed,
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=True,
        moe_capacity_factor=None,  # dropless, over the experts this chip holds
        moe_held=None if held == (0, routed) else held,
        moe_score="softmax",
        moe_aux_coef=float(config["router_aux_loss_coef"]),
        bd_block_length=block_length,
        bd_noise_seed=noise_seed,
    )


def loss(config: Dict[str, Any]):
    """(params, batch) -> (loss, counters) as the train step takes it."""
    from torchft_tpu.models.transformer import loss_and_counters

    cfg = transformer_config(config)
    return lambda p, b: loss_and_counters(p, b, cfg)


def train_step(config: Dict[str, Any], device):
    """(ftmesh, TrainStep) of one replica group on one device.  The loss hands
    out the model's counters (the masked share, the mean weight, the live
    pairs' share, tokens per expert, assignments that fell on held experts,
    assignments dropped), which `ft_step` lands in the program's
    `step_summary` records."""
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), loss(config), loss_has_counters=True)


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's.
    `bd_attn`: attention over the doubled stream's live tiles, forward and
    backward (`attn`, the dense files' `tpuft_fa_*`, stays: it must read
    nothing here)."""
    return dict(_DENSE.kernel_names(), gmm=lambda op: "tpuft_gmm_" in op, bd_attn=lambda op: "tpuft_bd_" in op)
