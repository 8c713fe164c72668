"""How a sparse mixture-of-experts LM configuration (the OLMoE family) is
handed to the program.

Turns the configuration file's published keys into the program's own settings
(`torchft_tpu.models.TransformerConfig`: dropless top-k routing, the QK-norm,
the published epsilon, both auxiliary losses) and builds the system under
test through the library's entry points.  The optimizer, the Manager and the
averager are the dense configurations' (`programs/dense_lm.py`, beside this
file).  Nothing here computes a result that is compared.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict

from benchmark import spec

_DENSE = spec._module("programs", "dense_lm", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
optimizer = _DENSE.optimizer
manager = _DENSE.manager
gradient_averager = _DENSE.gradient_averager


def transformer_config(config: Dict[str, Any]):
    import dataclasses

    if config.get("clip_qkv") is not None:
        raise ValueError("the program's attention does not clip q, k, v")
    return dataclasses.replace(
        _DENSE.transformer_config(config),
        qk_norm=True,
        rms_eps=float(config["rms_norm_eps"]),
        moe_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=bool(config["norm_topk_prob"]),
        moe_capacity_factor=None,  # dropless: every expert on the one chip
        moe_aux_coef=float(config["router_aux_loss_coef"]),
        moe_z_coef=float(config["router_z_loss_coef"]),
    )


def train_step(config: Dict[str, Any], device):
    """(ftmesh, TrainStep) of one replica group on one device.  The loss
    hands out the model's counters (tokens per expert, assignments dropped),
    which `ft_step` lands in the program's `step_summary` records."""
    from torchft_tpu.models.transformer import loss_and_counters
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    cfg = transformer_config(config)
    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    step = TrainStep(ftmesh, optimizer(config), lambda p, b: loss_and_counters(p, b, cfg),
                     loss_has_counters=True)
    return ftmesh, step


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's."""
    return dict(_DENSE.kernel_names(), gmm=lambda op: "tpuft_gmm_" in op)
