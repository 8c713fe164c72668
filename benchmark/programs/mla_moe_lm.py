"""How a latent-attention, shared-expert, sigmoid-routed sparse LM
configuration (the DeepSeek-V3 family: Moonlight-16B-A3B) is handed to the
program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig`: MLA at its three head widths, the
leading dense layers, the bias-corrected sigmoid router with its scale, the
shared expert, and WHICH of the router's experts this chip holds) and builds
the system under test through the library's entry points.  The optimizer, the
Manager and the averager are the dense configurations' (`programs/dense_lm.py`,
beside this file).  The router's bias is a buffer, not a weight: it comes from
the configuration (`reference.router_bias`, the same array the reference
uses) and is handed to the loss as a constant, outside the tree that the
gradient and AdamW see.  Nothing here computes a result that is compared.
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


def transformer_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from torchft_tpu.models import TransformerConfig

    if config.get("q_lora_rank") is not None:
        raise ValueError("the program's latent attention has no low-rank query path")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("the program's router chooses over one group")
    if (config["scoring_func"], config["topk_method"]) != ("sigmoid", "noaux_tc"):
        raise ValueError("this file hands over the bias-corrected sigmoid router")
    if config["num_key_value_heads"] != config["num_attention_heads"] or config.get("moe_layer_freq", 1) != 1:
        raise ValueError("latent attention has a key per query head, and every layer after the dense ones is sparse")
    training, program = config["training"], config["program"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    share = config.get("expert_parallel") or {}
    routed = share.get("router_outputs", config["n_routed_experts"])
    held = (share.get("first_expert_held", 0), config["n_routed_experts"])
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        dtype=dtypes[training["compute_dtype"]],
        param_dtype=dtypes[training["param_dtype"]],
        remat=program["remat"],
        remat_keeps_attention=bool(program.get("remat_keeps_attention", False)),
        scan_unroll=program["scan_unroll"],
        rms_eps=float(config["rms_norm_eps"]),
        mla_kv_rank=config["kv_lora_rank"],
        mla_nope_dim=config["qk_nope_head_dim"],
        mla_rope_dim=config["qk_rope_head_dim"],
        mla_v_dim=config["v_head_dim"],
        moe_experts=routed,
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=bool(config["norm_topk_prob"]),
        moe_capacity_factor=None,  # dropless, over the experts this chip holds
        moe_held=None if held == (0, routed) else held,
        moe_score="sigmoid",
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_shared_experts=config["n_shared_experts"],
        moe_aux_coef=float(config["aux_loss_alpha"]),
        moe_dense_layers=config["first_k_dense_replace"],
        dense_d_ff=config["intermediate_size"],
    )


def router_bias(config: Dict[str, Any]):
    """The constant [sparse layers, router outputs] the router adds to its
    scores before it chooses: the reference's own array."""
    return spec._module("reference", config["architecture"], _BENCH_DIR).router_bias(config)


def loss(config: Dict[str, Any]):
    """(params, batch) -> (loss, counters) as the train step takes it."""
    import jax.numpy as jnp

    from torchft_tpu.models.transformer import loss_and_counters

    cfg, bias = transformer_config(config), jnp.asarray(router_bias(config))
    return lambda p, b: loss_and_counters(p, b, cfg, router_bias=bias)


def train_step(config: Dict[str, Any], device):
    """(ftmesh, TrainStep) of one replica group on one device.  The loss
    hands out the model's counters (tokens per expert, assignments that fell
    on held experts, assignments dropped), which `ft_step` lands in the
    program's `step_summary` records."""
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), loss(config), loss_has_counters=True)


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's.  Latent
    attention runs the `tpuft_fa_*` kernels at 192 / 128."""
    return dict(_DENSE.kernel_names(), gmm=lambda op: "tpuft_gmm_" in op)
