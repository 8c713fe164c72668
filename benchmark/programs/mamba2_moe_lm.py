"""How a sparse LM whose blocks are a Mamba-2 mixer alone, attention alone or
experts alone (the causal tower of Nemotron-Labs-TwoTower-30B-A3B-Base,
`model_type: nemotron_h`) is handed to the program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig` with a `pattern` read off
`hybrid_override_pattern`, a block a letter: `M` a `LayerKind` whose mixer is
"mamba2" — 64 heads of 64 in 8 groups over a state of 128, kernel-4 convolution,
chunks of 128 — and no feed-forward; `*` plain grouped-query attention with
`rotary_fraction` 0 and no feed-forward; `E` a kind with NO mixer whose
feed-forward is the experts; a stack a kind, named as the reference names them;
un-gated ReLU^2 experts and shared expert, the bias-corrected sigmoid router
with its scale, and WHICH of the router's experts this chip holds) and builds
the system under test through the library's entry points.  The optimizer, the
Manager and the averager are the dense configurations'
(`programs/dense_lm.py`, beside this file).  The router's bias is a buffer, not
a weight: the reference's own array, a row an expert block, handed to the loss
as a constant.  It raises on every key it does not honour.  Nothing here
computes a result that is compared.
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

    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("the program's router chooses over one group")
    if config["tie_word_embeddings"]:
        raise ValueError("this file hands over an untied head")
    if config.get("sliding_window") is not None:
        raise ValueError("this file hands over attention over all of the past")
    if config["residual_in_fp32"]:
        raise ValueError("the program's residual stream is in the compute type")
    low, high = config["time_step_limit"]
    if low not in (0, 0.0) or high is not None:
        raise ValueError("the program's time step is softplus's own: no clamp")
    if any(config[key] for key in ("use_bias", "mamba_proj_bias", "mlp_bias", "attention_bias")):
        raise ValueError("the program's products have no bias")
    if not config["use_conv_bias"]:
        raise ValueError("the program's convolution has a bias")
    if config["mlp_hidden_act"] != "relu2" or config["mamba_hidden_act"] != "silu":
        raise ValueError("this file hands over un-gated ReLU^2 feed-forwards and SiLU inside the mixer")
    if not config["norm_topk_prob"]:
        raise ValueError("this file hands over the renormalised gates")
    if config["moe_shared_expert_intermediate_size"] % config["moe_intermediate_size"] or config["n_shared_experts"] != 1:
        raise ValueError("the program's shared expert is a whole number of expert widths, one expert")
    if config["mamba_num_heads"] % config["n_groups"]:
        raise ValueError("a group is a whole number of heads")
    # every block's letter and the stack's name as the reference reads them off the pattern (it raises on any
    # letter but M, E and *: `-`, a dense feed-forward block, is not in this model and is refused): the two
    # share the tree's layout
    reference = spec._module("reference", config["architecture"], _BENCH_DIR)
    theta = float(config["rope_theta"])  # carried, and read by nothing: no block rotates
    kinds = {
        "M": LayerKind(reference.stack_of("M"), False, config["mamba_num_heads"], theta, rotary_fraction=0.0,
                       mixer="mamba2", feed_forward=False),
        "*": LayerKind(reference.stack_of("*"), False, config["num_attention_heads"], theta, rotary_fraction=0.0,
                       feed_forward=False),
        "E": LayerKind(reference.stack_of("E"), True, config["num_attention_heads"], theta, rotary_fraction=0.0,
                       mixer="none"),
    }
    pattern = tuple(kinds[letter] for letter in reference.layer_plan(config))
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
        head_dim=config["head_dim"],
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        dtype=dtypes[training["compute_dtype"]],
        param_dtype=dtypes[training["param_dtype"]],
        remat=program["remat"],
        remat_keeps_attention=bool(program.get("remat_keeps_attention", False)),
        scan_unroll=program["scan_unroll"],
        rms_eps=float(config["layer_norm_epsilon"]),
        pattern=pattern,
        ssm_head_dim=config["mamba_head_dim"],
        ssm_groups=config["n_groups"],
        ssm_state=config["ssm_state_size"],
        ssm_conv=config["conv_kernel"],
        ssm_chunk=config["chunk_size"],
        moe_experts=routed,
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=True,
        moe_capacity_factor=None,  # dropless, over the experts this chip holds
        moe_held=None if held == (0, routed) else held,
        moe_score="sigmoid",
        moe_route_scale=float(config["routed_scaling_factor"]),
        moe_shared_experts=config["moe_shared_expert_intermediate_size"] // config["moe_intermediate_size"],
        moe_aux_coef=0.0,  # the published config carries no coefficient
        moe_activation="relu2",
    )


def router_bias(config: Dict[str, Any]):
    """The constant [expert blocks, router outputs] the router adds to its
    scores before it chooses: the reference's own array."""
    return spec._module("reference", config["architecture"], _BENCH_DIR).router_bias(config)


def loss(config: Dict[str, Any]):
    """(params, batch) -> (loss, counters) as the train step takes it."""
    import jax.numpy as jnp

    from torchft_tpu.models.transformer import loss_and_counters

    cfg, bias = transformer_config(config), jnp.asarray(router_bias(config))
    return lambda p, b: loss_and_counters(p, b, cfg, router_bias=bias)


def train_step(config: Dict[str, Any], device):
    """(ftmesh, TrainStep) of one replica group on one device.  The loss hands
    out the model's counters (tokens per expert, assignments that fell on held
    experts, assignments dropped, the hidden units ReLU left above zero, the
    mean decay of the Mamba-2 blocks), which `ft_step` lands in the program's
    `step_summary` records."""
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), loss(config), loss_has_counters=True)


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's.  The
    attention block runs the `tpuft_fa_*` kernels (`attn`), the state-space
    recurrence the `tpuft_ssd_*` kernels."""
    return dict(_DENSE.kernel_names(), gmm=lambda op: "tpuft_gmm_" in op, ssd=lambda op: "tpuft_ssd_" in op)
