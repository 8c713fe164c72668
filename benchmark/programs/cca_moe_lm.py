"""How a sparse LM with compressed convolutional attention, a router that
carries a state, a choice that takes no expert, learned residual merges and a
tied head (ZAYA1-8B, `model_type: zaya`) is handed to the program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig` with a `pattern` of one `LayerKind`
whose mixer is "cca" — 8 query heads on 2 KV heads of 128 under two causal
convolutions of kernel 2, RoPE on half a head's columns; the router as an MLP
of `router_hidden_size` with its state carried by the layer loop; softmax
scores with a choice bias, one expert a token un-renormalised, the router's last
output the choice that takes none; the merges' vectors; the head read off the
embedding; and WHICH of the router's experts this chip holds) and builds the
system under test through the library's entry points.  The optimizer, the
Manager and the averager are the dense configurations' (`programs/dense_lm.py`,
beside this file).  It raises on every key it does not honour.  Nothing here
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

    n = config["num_hidden_layers"]
    if any(kind != "hybrid" for kind in config["layer_types"][:n]):
        raise ValueError(f"the program runs layers of the one kind `hybrid`: {config['layer_types'][:n]}")
    if config["num_experts_per_tok"] != 1:
        raise ValueError("this file hands over the router that sends a token to one expert or to none")
    if config.get("sliding_window") is not None:
        raise ValueError("compressed attention has no window here")
    if not config["tie_word_embeddings"]:
        raise ValueError("this file hands over a head that is the embedding itself")
    if config.get("attention_bias") or config.get("lm_head_bias"):
        raise ValueError("the program's projections and head have no bias")
    if (config["cca_time0"], config["cca_time1"]) != (2, 2):
        raise ValueError("the program's two convolutions have kernel 2")
    if config["hidden_act"] != "silu":
        raise ValueError("the program's experts are SwiGLUs")
    rope = config["rope_parameters"]["hybrid"]
    if rope["rope_type"] != "default":
        raise ValueError(f"the program computes no rope_type {rope['rope_type']!r}")
    if float(rope["partial_rotary_factor"]) != float(config["partial_rotary_factor"]):
        raise ValueError("the layers' rotary fraction is the model's")
    training, program = config["training"], config["program"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    share = config.get("expert_parallel") or {}
    routed = share.get("routed_experts", config["num_experts"])
    held = (share.get("first_expert_held", 0), config["num_experts"])
    kind = LayerKind("layers", True, config["num_attention_heads"], float(rope["rope_theta"]),
                     rotary_fraction=float(rope["partial_rotary_factor"]), mixer="cca")
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=n,
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
        rms_eps=float(config["rms_norm_eps"]),
        pattern=(kind,) * n,
        moe_experts=routed,
        moe_top_k=1,
        moe_norm_topk=False,  # renormalised, a top-1 gate is 1 and the router learns nothing
        moe_capacity_factor=None,  # dropless, over the experts this chip holds
        moe_held=None if held == (0, routed) else held,
        moe_score="softmax",
        moe_router_state=config["router_hidden_size"],
        moe_skip=True,
        moe_aux_coef=0.0,  # the family balances by the bias
        scaled_merge=True,
        tied_head=True,
    )


def router_bias(config: Dict[str, Any]):
    """The constant [layers, router outputs] the router adds to its
    probabilities before it chooses: the reference's own array."""
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
    on held experts, assignments dropped, positions that took no expert),
    which `ft_step` lands in the program's `step_summary` records."""
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), loss(config), loss_has_counters=True)


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's.
    Compressed attention runs the `tpuft_fa_*` kernels (`attn`) at 8 heads."""
    return dict(_DENSE.kernel_names(), gmm=lambda op: "tpuft_gmm_" in op)
