"""How a sparse LM whose layers mix window and full attention at different head
counts (Laguna-XS.2, `model_type: laguna`) is handed to the program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig` with a `pattern`: per layer a
`LayerKind` — full attention at 48 heads under YaRN on half a head's columns,
or a window of 512 at 64 heads under plain RoPE; a dense or a sparse
feed-forward — each kind stacked under a name of its own; the per-head output
gate; the sigmoid router with its scale and no choice bias; the shared expert;
and WHICH of the router's experts this chip holds) and builds the system under
test through the library's entry points.  The optimizer, the Manager and the
averager are the dense configurations' (`programs/dense_lm.py`, beside this
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

# The period, and the stack a kind of layer lives under: the weight tree's layout, which the program and the
# reference have in common (`reference/swa_moe_lm.py` makes the weights).
_REFERENCE = spec._module("reference", "swa_moe_lm", _BENCH_DIR)
PERIOD, STACKS = _REFERENCE.PERIOD, _REFERENCE.STACK_OF


def layer_kinds(config: Dict[str, Any]):
    """One `LayerKind` a layer, the first `num_hidden_layers` of the published lists."""
    from torchft_tpu.models import LayerKind

    n = config["num_hidden_layers"]
    attention, ffn = config["layer_types"][:n], config["mlp_layer_types"][:n]
    heads = config["num_attention_heads_per_layer"][:n]
    if tuple(attention) != tuple(PERIOD[i % len(PERIOD)] for i in range(n)):
        raise ValueError(f"layer_types is not the period {PERIOD}: {attention}")
    rope = config["rope_parameters"]
    kinds = []
    for a, f, h in zip(attention, ffn, heads):
        if (a, f) not in STACKS:
            raise ValueError(f"no stack for a {a} layer with a {f} feed-forward")
        r = rope[a]
        fraction = float(r.get("partial_rotary_factor", 1.0))
        if r["rope_type"] == "yarn":
            yarn = (float(r["factor"]), int(r["original_max_position_embeddings"]), float(r["beta_fast"]),
                    float(r["beta_slow"]), float(r["attention_factor"]))
        elif r["rope_type"] == "default":
            yarn = None
        else:
            raise ValueError(f"the program computes no rope_type {r['rope_type']!r}")
        window = config["sliding_window"] if a == "sliding_attention" else None
        kinds.append(LayerKind(STACKS[(a, f)], f == "sparse", h, float(r["rope_theta"]), window=window,
                               rotary_fraction=fraction, yarn=yarn))
    if len({(k.stack, k.n_heads) for k in kinds}) != len({k.stack for k in kinds}):
        raise ValueError("a kind of layer has one head count")
    return tuple(kinds)


def transformer_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from torchft_tpu.models import TransformerConfig

    if config.get("moe_apply_router_weight_on_input"):
        raise ValueError("the program's gates weight the experts' outputs")
    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        raise ValueError("the program's projections have no bias and its head is untied")
    if config["shared_expert_intermediate_size"] % config["moe_intermediate_size"]:
        raise ValueError("the shared expert is a whole number of routed experts wide")
    training, program = config["training"], config["program"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    share = config.get("expert_parallel") or {}
    routed = share.get("router_outputs", config["num_experts"])
    held = (share.get("first_expert_held", 0), config["num_experts"])
    kinds = layer_kinds(config)
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["moe_intermediate_size"],
        dense_d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        dtype=dtypes[training["compute_dtype"]],
        param_dtype=dtypes[training["param_dtype"]],
        remat=program["remat"],
        remat_keeps_attention=bool(program.get("remat_keeps_attention", False)),
        scan_unroll=program["scan_unroll"],
        rms_eps=float(config["rms_norm_eps"]),
        pattern=kinds,
        attn_head_gate=bool(config["gating"]),
        moe_experts=routed,
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=True,
        moe_capacity_factor=None,  # dropless, over the experts this chip holds
        moe_held=None if held == (0, routed) else held,
        moe_score="sigmoid",
        moe_route_scale=float(config["moe_routed_scaling_factor"]),
        moe_shared_experts=config["shared_expert_intermediate_size"] // config["moe_intermediate_size"],
        moe_aux_coef=float(config["aux_loss_alpha"]),
    )


def loss(config: Dict[str, Any]):
    """(params, batch) -> (loss, counters) as the train step takes it."""
    from torchft_tpu.models.transformer import loss_and_counters

    cfg = transformer_config(config)
    return lambda p, b: loss_and_counters(p, b, cfg)


def train_step(config: Dict[str, Any], device):
    """(ftmesh, TrainStep) of one replica group on one device.  The loss
    hands out the model's counters (tokens per expert, assignments that fell
    on held experts, assignments dropped, the window layers' pairs), which
    `ft_step` lands in the program's `step_summary` records."""
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), loss(config), loss_has_counters=True)


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's.  The
    full layers run the `tpuft_fa_*` kernels (`attn`), the window layers the
    same bodies on the band walk under `tpuft_swa_*` (`swa`)."""
    return dict(_DENSE.kernel_names(), gmm=lambda op: "tpuft_gmm_" in op, swa=lambda op: "tpuft_swa_" in op)
