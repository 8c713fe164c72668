"""How a sparse LM whose router reads the layer's input before attention, whose
experts are ReGLU and whose layers mix window attention under RoPE with
un-rotated full attention (SmallThinker-21BA3B-Instruct) is handed to the
program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig` with a `pattern`: per layer a
`LayerKind` — full causal attention with no position term, or a window of 4,096
under plain RoPE at theta 1.5e6, both at 28 query heads over 4 KV heads and
both sparse — each kind stacked under a name of its own; the early router; the
ReLU gate; the softmax over the kept six; and WHICH of the router's experts
this chip holds) and builds the system under test through the library's entry
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

# The period, and the stack a kind of layer lives under: the weight tree's layout, which the program and the
# reference have in common (`reference/early_router_moe_lm.py` makes the weights).
_REFERENCE = spec._module("reference", "early_router_moe_lm", _BENCH_DIR)
PERIOD, STACKS = _REFERENCE.PERIOD, _REFERENCE.STACK_OF


def layer_kinds(config: Dict[str, Any]):
    """One `LayerKind` a layer, the first `num_hidden_layers` of the two published layouts."""
    from torchft_tpu.models import LayerKind

    n = config["num_hidden_layers"]
    rope, window = list(config["rope_layout"][:n]), list(config["sliding_window_layout"][:n])
    if len(window) != n or window != [PERIOD[i % len(PERIOD)] for i in range(n)]:
        raise ValueError(f"sliding_window_layout is not the period {PERIOD}: {window}")
    if rope != window:
        raise ValueError("the program turns the window layers and no other: rope_layout is not sliding_window_layout")
    if config.get("rope_scaling") is not None:
        raise ValueError(f"the program computes no rope_scaling {config['rope_scaling']!r} for this architecture")
    heads, theta = config["num_attention_heads"], float(config["rope_theta"])
    full = LayerKind(STACKS["full_attention"], True, heads, theta, window=None, rotary_fraction=0.0)
    band = LayerKind(STACKS["sliding_attention"], True, heads, theta, window=int(config["sliding_window_size"]))
    return tuple(band if flag else full for flag in window)


def transformer_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from torchft_tpu.models import TransformerConfig

    if config.get("tie_word_embeddings"):
        raise ValueError("the program's head for this architecture is untied")
    if not config["norm_topk_prob"]:
        raise ValueError("the program's gates are normalised over the kept experts")
    if not config["moe_primary_router_apply_softmax"]:
        raise ValueError("sigmoid-then-normalise over the kept experts is not written: the gates are a softmax")
    training, program = config["training"], config["program"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    share = config.get("expert_parallel") or {}
    routed = share.get("router_outputs", config["moe_num_primary_experts"])
    held = (share.get("first_expert_held", 0), config["moe_num_primary_experts"])
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["moe_ffn_hidden_size"],
        max_seq=config["max_position_embeddings"],
        dtype=dtypes[training["compute_dtype"]],
        param_dtype=dtypes[training["param_dtype"]],
        remat=program["remat"],
        remat_keeps_attention=bool(program.get("remat_keeps_attention", False)),
        scan_unroll=program["scan_unroll"],
        rms_eps=float(config["rms_norm_eps"]),
        pattern=layer_kinds(config),
        moe_experts=routed,
        moe_top_k=config["moe_num_active_primary_experts"],
        moe_norm_topk=True,  # a softmax over the kept six IS the softmax over all, renormalised over the six
        moe_capacity_factor=None,  # dropless, over the experts this chip holds
        moe_held=None if held == (0, routed) else held,
        moe_score="softmax",
        moe_aux_coef=0.0,
        moe_router_early=True,
        moe_activation="relu",
    )


def loss(config: Dict[str, Any]):
    """(params, batch) -> (loss, counters) as the train step takes it."""
    from torchft_tpu.models.transformer import loss_and_counters

    cfg = transformer_config(config)
    return lambda p, b: loss_and_counters(p, b, cfg)


def train_step(config: Dict[str, Any], device):
    """(ftmesh, TrainStep) of one replica group on one device.  The loss
    hands out the model's counters (tokens per expert, assignments that fell
    on held experts, assignments dropped, the hidden units ReLU left above
    zero), which `ft_step` lands in the program's `step_summary` records."""
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), loss(config), loss_has_counters=True)


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's.  The
    full layers run the `tpuft_fa_*` kernels (`attn`), the window layers the
    same bodies on the band walk under `tpuft_swa_*` (`swa`)."""
    return dict(_DENSE.kernel_names(), gmm=lambda op: "tpuft_gmm_" in op, swa=lambda op: "tpuft_swa_" in op)
