"""Standalone: forms of the half-split RoPE rotation in ONE decoder layer at a
cell's widths, the kernels on, the gradient program timed on the chip form by
form and its gradients compared bit for bit with the first form's.

    chiprun -- python tools/rope_probe.py                           # every form, every kind of layer
    python tools/rope_probe.py --compile split:window tree:window   # for a described v5e, no chip: what XLA leaves

A form is `<form>:<layer>`.  Layers: `window` (64 heads of 128 columns over 8
KV heads under a window of 512, 16,384 positions: Laguna's), `full` (48 heads,
64 of 128 columns under YaRN), `latent` (Moonlight's: 16 heads whose 64 rotary
columns sit beside 128 that do not turn, one rotary key, 2 x 8,192
positions).  Forms: `split`, the two-halves form the tree had before PR 39;
`tree`, the tree as it stands (a whole head's halves swapped in float32
forward, in the cotangent's own dtype backward); `f32`, the swap after the
cast in both directions; `b16`, the swap before the cast in both directions
(the product before it then rounds q to bf16: other values).  No cell runs
this; PERF.md section 6 (PR 39) cites its readings.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def install(name: str, T, saved: dict) -> None:
    """Patches `T` (models.rope) to the form `name`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    for key, value in saved.items():
        setattr(T, key, value)
    if name == "tree":
        return
    if name == "split":
        def _turn(x, positions, inv_freq, factor, rot):
            half = rot // 2
            angles = positions[..., None].astype(jnp.float32) * inv_freq
            cos, sin = (jnp.cos(angles) * factor)[:, :, None, :], (jnp.sin(angles) * factor)[:, :, None, :]
            xf = x.astype(jnp.float32)
            x1, x2 = xf[..., :half], xf[..., half:rot]
            return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, xf[..., rot:]], axis=-1).astype(x.dtype)

        T._turn = _turn
        return
    early = {"f32": (False, False), "b16": (True, True)}[name]  # swap before the cast: (forward, backward)

    def swapped(x, half, before_the_cast):
        return T._swap_halves(x, half).astype(jnp.float32) if before_the_cast else T._swap_halves(x.astype(jnp.float32), half)

    def _turn(x, positions, inv_freq, factor, rot):
        D, half = x.shape[-1], rot // 2
        lane = np.arange(D)
        angles = positions[..., None].astype(jnp.float32) * jnp.take(inv_freq, lane % half)
        cos = jnp.where(lane < rot, jnp.cos(angles) * np.float32(factor), 1.0)[:, :, None, :]
        sin = jnp.where(lane < rot, jnp.sin(angles) * np.where(lane < half, -factor, factor).astype(np.float32), 0.0)[:, :, None, :]
        whole = jax.custom_vjp(lambda x, cos, sin: T._turned(x, swapped(x, half, early[0]), cos, sin, rot))
        whole.defvjp(lambda x, cos, sin: (whole(x, cos, sin), (cos, sin)),
                     lambda tables, g: (T._turned(g, swapped(g, half, early[1]), tables[0], -tables[1], rot), None, None))
        return whole(x, cos, sin)

    T._turn = _turn


def layer_config(which: str):
    from torchft_tpu.models import LayerKind, TransformerConfig

    if which == "latent":
        return TransformerConfig(vocab_size=1024, d_model=2048, n_layers=1, n_heads=16, n_kv_heads=16, d_ff=256, max_seq=8192,
                                 rope_theta=5e4, remat=True, remat_keeps_attention=True, scan_unroll=8,
                                 mla_kv_rank=512, mla_nope_dim=128, mla_rope_dim=64, mla_v_dim=128), (2, 8192)
    if which == "full":
        kind = LayerKind("layers", False, 48, 5e5, rotary_fraction=0.5, yarn=(64.0, 4096, 64.0, 1.0, 1.4158883083359672))
    else:
        kind = LayerKind("layers", False, 64, 1e4, window=512)
    return TransformerConfig(vocab_size=1024, d_model=2048, n_layers=1, n_heads=kind.n_heads, n_kv_heads=8, head_dim=128,
                             d_ff=256, max_seq=16_384, remat=True, remat_keeps_attention=True, attn_head_gate=True,
                             pattern=(kind,), scan_unroll=8), (1, 16_384)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("forms", nargs="*")
    ap.add_argument("--compile", action="store_true", help="compile for a described v5e here and write the texts; nothing runs")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "rope_probe"))
    args = ap.parse_args()
    if args.compile:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models import init_params
    from torchft_tpu.models import rope as T
    from torchft_tpu.models.transformer import loss_and_counters

    os.makedirs(args.out, exist_ok=True)
    if args.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        from torchft_tpu.ops import _pallas_util

        jax.config.update("jax_enable_compilation_cache", False)
        _pallas_util.on_tpu = lambda: True
        place = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    else:
        assert jax.devices()[0].platform == "tpu", jax.devices()
        print("device", jax.devices()[0].device_kind, flush=True)
    saved = {"_turn": T._turn}
    forms = args.forms or [f"{f}:{w}" for w in ("window", "full") for f in ("split", "tree", "f32", "b16")] + ["split:latent", "tree:latent"]
    reference = {}
    for name in forms:
        form, _, which = name.partition(":")
        which = which or "window"
        install(form, T, saved)
        cfg, tokens_shape = layer_config(which)
        fn = jax.jit(jax.value_and_grad(lambda p, b: loss_and_counters(p, b, cfg)[0]))
        if args.compile:
            shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
            params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=place), shapes)
            tokens = jax.ShapeDtypeStruct(tokens_shape, jnp.int32, sharding=place)
            t0 = time.time()
            compiled = fn.lower(params, {"tokens": tokens, "targets": tokens}).compile()
            path = os.path.join(args.out, f"{form}_{which}.hlo")
            with open(path, "w", encoding="utf-8") as f:
                f.write(compiled.as_text())
            print(f"{name}: compiled in {time.time() - t0:.1f} s, temporaries {compiled.memory_analysis().temp_size_in_bytes / 1e6:.0f} MB, {path}", flush=True)
            continue
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, tokens_shape), jnp.int32)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
        t0 = time.time()
        loss, grads = jax.block_until_ready(fn(params, batch))
        first = time.time() - t0
        times = []
        for _ in range(args.steps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(params, batch))
            times.append((time.perf_counter() - t0) * 1e3)
        leaves = {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(v, np.float32) for path, v in jax.tree_util.tree_leaves_with_path(grads)}
        against = reference.setdefault(which, (float(loss), leaves))
        worst = max(float(np.abs(v - against[1][k]).max() / (np.abs(against[1][k]).max() + 1e-30)) for k, v in leaves.items())
        same = all(np.array_equal(v, against[1][k]) for k, v in leaves.items())
        print(f"{name}: step median {np.median(times):.3f} ms (min {min(times):.3f}, max {max(times):.3f}; first call {first:.1f} s), loss {float(loss)!r}"
              f" (first form of this kind: {against[0]!r}), gradients {'bitwise the first form`s' if same else f'within {worst:.2e} of the first form`s, relative to a leaf`s largest'}", flush=True)


if __name__ == "__main__":
    main()
