"""How a sparse LM that mixes Gated DeltaNet with output-gated softmax
attention (Qwen3-Next-80B-A3B, `model_type: qwen3_next`) is handed to the
program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig` with a `pattern` read off
`full_attention_interval`: a `LayerKind` whose mixer is "gdn" — the
`linear_num_value_heads` value heads over `linear_num_key_heads` key heads under
a kernel-`linear_conv_kernel_dim` convolution — for three layers of four, one
whose mixer is "attention" at `num_attention_heads` / `num_key_value_heads`
heads of `head_dim`, the leading `partial_rotary_factor` of a head rotated,
under the per-head QK-norm and the output gate a column, for the fourth; a
stack a kind, named as the reference names them; the zero-centred norm weights;
the softmax router with renormalised gates, the shared expert under its sigmoid
gate, and WHICH of the router's experts this chip holds) and builds the system
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


def transformer_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from torchft_tpu.models import LayerKind, TransformerConfig

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
    if config["shared_expert_intermediate_size"] != config["moe_intermediate_size"]:
        raise ValueError("the shared expert is as wide as a routed one: one SwiGLU of moe_intermediate_size")
    if config.get("num_nextn_predict_layers") or config.get("mtp_num_hidden_layers"):
        raise ValueError("the program has no extra prediction layers")
    # every layer's mixer and the stack's name as the reference reads them off the interval: the two share the tree's layout
    reference = spec._module("reference", config["architecture"], _BENCH_DIR)
    heads = {"gdn": config["linear_num_value_heads"], "attention": config["num_attention_heads"]}
    pattern = [LayerKind(reference.stack_of(mixer, sparse), sparse, heads[mixer], float(config["rope_theta"]),
                         rotary_fraction=float(config["partial_rotary_factor"]), mixer=mixer)
               for mixer, sparse in reference.layer_plan(config)]
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
        attn_out_gate=True,
        norm_unit_offset=True,
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        dtype=dtypes[training["compute_dtype"]],
        param_dtype=dtypes[training["param_dtype"]],
        remat=program["remat"],
        remat_keeps_attention=bool(program.get("remat_keeps_attention", False)),
        scan_unroll=program["scan_unroll"],
        rms_eps=float(config["rms_norm_eps"]),
        pattern=tuple(pattern),
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        gdn_conv=config["linear_conv_kernel_dim"],
        moe_experts=routed,
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=True,
        moe_capacity_factor=None,  # dropless, over the experts this chip holds
        moe_held=None if held == (0, routed) else held,
        moe_score="softmax",
        moe_shared_experts=1,
        moe_shared_gate=True,
        moe_aux_coef=float(config["router_aux_loss_coef"]),
    )


def loss(config: Dict[str, Any]):
    """(params, batch) -> (loss, counters) as the train step takes it."""
    from torchft_tpu.models.transformer import loss_and_counters

    cfg = transformer_config(config)
    return lambda p, b: loss_and_counters(p, b, cfg)


def train_step(config: Dict[str, Any], device):
    """(ftmesh, TrainStep) of one replica group on one device.  The loss
    hands out the model's counters (tokens per expert, assignments that fell
    on held experts, assignments dropped, the mean decay of the Gated DeltaNet
    layers, the mean of the shared expert's gate), which `ft_step` lands in
    the program's `step_summary` records."""
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), loss(config), loss_has_counters=True)


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's.  The
    attention layer runs the `tpuft_fa_*` kernels (`attn`) at 16 / 2 heads of
    256; `gdn`: the gated delta rule's scan with a decay a head, whatever
    kernels `ops.delta_attention.kda` runs it on (`tpuft_kda_*` today, the
    channel form under a broadcast decay)."""
    return dict(_DENSE.kernel_names(), gmm=lambda op: "tpuft_gmm_" in op, gdn=lambda op: "tpuft_kda_" in op)
