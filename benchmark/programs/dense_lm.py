"""How a dense-LM configuration is handed to the program.

The one place that turns a configuration file's published keys into the
program's own settings (`torchft_tpu.models.TransformerConfig`) and builds the
system under test through the library's entry points.  Nothing here computes a
result that is compared: weights, batches and the reference are the
benchmark's own.
"""

from __future__ import annotations

from datetime import timedelta
from typing import Any, Callable, Dict


def transformer_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from torchft_tpu.models import TransformerConfig

    training, program = config["training"], config["program"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    heads = config["num_attention_heads"]
    if config.get("head_dim", config["hidden_size"] // heads) * heads != config["hidden_size"]:
        raise ValueError("the program's model derives the head size from hidden / heads")
    return TransformerConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        dtype=dtypes[training["compute_dtype"]],
        param_dtype=dtypes[training["param_dtype"]],
        remat=program["remat"],
        scan_unroll=program["scan_unroll"],
    )


def optimizer(config: Dict[str, Any]):
    import optax

    training = config["training"]
    if training["optimizer"] != "adamw":
        raise ValueError(f"no optimizer {training['optimizer']!r} here")
    return optax.adamw(training["learning_rate"])


def train_step(config: Dict[str, Any], device):
    """(ftmesh, TrainStep) of one replica group on one device."""
    from torchft_tpu.models import loss_fn
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    cfg = transformer_config(config)
    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    return ftmesh, TrainStep(ftmesh, optimizer(config), lambda p, b: loss_fn(p, b, cfg))


def manager(state: Dict[str, Any], replica_id: str, timeout_s: float = 120.0):
    """A replica group's Manager as a user gets it: every setting the
    program's default (f32 wire, default ring engine, lanes and transport,
    async quorum), the lighthouse from `TPUFT_LIGHTHOUSE`.  `init_sync=False`:
    every group starts from the seed's weights, so nothing heals."""
    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.manager import Manager

    def load(sd) -> None:
        state["params"], state["opt"] = sd["params"], sd["opt"]

    return Manager(
        collective=TCPCollective(timeout=timeout_s),
        load_state_dict=load,
        state_dict=lambda: {"params": state["params"], "opt": state["opt"]},
        min_replica_size=1,
        timeout=timedelta(seconds=timeout_s),
        quorum_timeout=timedelta(seconds=timeout_s),
        rank=0,
        world_size=1,
        replica_id=replica_id,
        checkpoint_transport=HTTPTransport(timeout=timeout_s),
        init_sync=False,
    )


def gradient_averager(mgr) -> Any:
    from torchft_tpu.ddp import GradientAverager

    return GradientAverager(mgr)


def kernel_names() -> Dict[str, Callable[[str], bool]]:
    """The stable names the program gives its pallas kernels; a device
    operation belongs to a kernel when its name contains the kernel's."""
    return {
        "attn": lambda op: any(k in op for k in ("tpuft_fa_fwd", "tpuft_fa_bwd_dkdv", "tpuft_fa_bwd_dq")),
        "ce": lambda op: any(k in op for k in ("tpuft_ce_lse", "tpuft_ce_dlogits")),
    }
