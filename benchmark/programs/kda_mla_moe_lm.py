"""How a sparse LM that mixes Kimi Delta Attention with unrotated latent
attention (Kimi-Linear-48B-A3B, `model_type: kimi_linear`) is handed to the
program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig` with a `pattern` read off
`linear_attn_config`'s two lists: a `LayerKind` whose mixer is "kda" — 32 heads
of 128 under kernel-4 convolutions and rank-128 gates — for the layers of
`kda_layers`, one whose mixer is "mla" with `rotary_fraction` 0 (`mla_use_nope`)
for those of `full_attn_layers`, the first `first_k_dense_replace` with a dense
feed-forward; a stack a kind, named as the reference names them; the
bias-corrected sigmoid router with its scale, the shared expert, and WHICH of
the router's experts this chip holds) and builds the system under test through
the library's entry points.  The optimizer, the Manager and the averager are
the dense configurations' (`programs/dense_lm.py`, beside this file).  The
router's bias is a buffer, not a weight: the reference's own array, a row a
sparse layer in the layers' order whatever their stack, handed to the loss as a
constant.  It raises on every key it does not honour.  Nothing here computes a
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


def transformer_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from torchft_tpu.models import LayerKind, TransformerConfig

    if config.get("q_lora_rank") is not None:
        raise ValueError("the program's latent attention has no low-rank query path")
    if config["num_expert_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("the program's router chooses over one group")
    if config["moe_router_activation_func"] != "sigmoid":
        raise ValueError("this file hands over the bias-corrected sigmoid router")
    if not config["mla_use_nope"]:
        raise ValueError("this file hands over latent attention without rotation")
    if config["num_nextn_predict_layers"] != 0:
        raise ValueError("the program has no extra prediction layers")
    if config["tie_word_embeddings"]:
        raise ValueError("this file hands over an untied head")
    if config["num_key_value_heads"] != config["num_attention_heads"] or config.get("moe_layer_freq", 1) != 1:
        raise ValueError("latent attention has a key per query head, and every layer after the dense ones is sparse")
    if config["hidden_act"] != "silu" or config.get("rope_scaling") is not None:
        raise ValueError("the program's feed-forwards are SwiGLUs, and nothing is rotated or scaled")
    linear, n = config["linear_attn_config"], config["num_hidden_layers"]
    # every layer's mixer and feed-forward and the stack's name as the reference reads them off the two lists
    # (it raises on a layer in neither or both): the two share the tree's layout
    reference = spec._module("reference", config["architecture"], _BENCH_DIR)
    heads = {"kda": linear["num_heads"], "mla": config["num_attention_heads"]}
    pattern = [LayerKind(reference.stack_of(mixer, sparse), sparse, heads[mixer], float(config["rope_theta"]),
                         rotary_fraction=0.0, mixer=mixer) for mixer, sparse in reference.layer_plan(config)]
    training, program = config["training"], config["program"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    share = config.get("expert_parallel") or {}
    routed = share.get("router_outputs", config["num_experts"])
    held = (share.get("first_expert_held", 0), config["num_experts"])
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=n,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["moe_intermediate_size"],
        max_seq=config["model_max_length"],
        dtype=dtypes[training["compute_dtype"]],
        param_dtype=dtypes[training["param_dtype"]],
        remat=program["remat"],
        remat_keeps_attention=bool(program.get("remat_keeps_attention", False)),
        scan_unroll=program["scan_unroll"],
        rms_eps=float(config["rms_norm_eps"]),
        pattern=tuple(pattern),
        mla_kv_rank=config["kv_lora_rank"],
        mla_nope_dim=config["qk_nope_head_dim"],
        mla_rope_dim=config["qk_rope_head_dim"],
        mla_v_dim=config["v_head_dim"],
        kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"],
        moe_experts=routed,
        moe_top_k=config["num_experts_per_token"],
        moe_norm_topk=bool(config["moe_renormalize"]),
        moe_capacity_factor=None,  # dropless, over the experts this chip holds
        moe_held=None if held == (0, routed) else held,
        moe_score="sigmoid",
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_shared_experts=config["num_shared_experts"],
        moe_aux_coef=float(config["aux_loss_alpha"]),
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
    on held experts, assignments dropped, the mean decay of the KDA layers),
    which `ft_step` lands in the program's `step_summary` records."""
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), loss(config), loss_has_counters=True)


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's.  Latent
    attention runs the `tpuft_fa_*` kernels (`attn`) at 192 / 128, the delta
    rule's scan the `tpuft_kda_*` kernels."""
    return dict(_DENSE.kernel_names(), gmm=lambda op: "tpuft_gmm_" in op, kda=lambda op: "tpuft_kda_" in op)
