"""The comparison that decides `correct` for one training step.

A seeded sample of gradient entries of the program's gradient program is set
against the plain reference on the same weights and batch.  The number that
decides, printed beside its limit in every run:

- `grad_rel`: over the sampled entries of every leaf of the weight tree, the
  L2 norm of the difference as a share of the reference's L2 norm, then the
  root mean square of those shares over the leaves (every leaf weighs the
  same, so a fault in one small matrix is not drowned by the embedding).

The limit is the configuration's (`correct` in its file) and was set from
readings on the chip: the largest value sound runs gave over a dozen seeds and
the smallest the lower-precision control gave (PERF.md section 2).  The loss
and the reference's are printed beside it (`loss_rel`) and decide nothing
beyond being finite: at random weights the loss sits at log(vocabulary) and
the fp8 control moved it by 3e-5 where sound runs reach 2e-5, so no limit on
it separates the two (same readings).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

SAMPLE_PER_LEAF = 4096


def sample_indices(seed: int, tree: Any) -> Dict[str, np.ndarray]:
    """Per leaf, `SAMPLE_PER_LEAF` flat indices drawn from the seed (all of a
    leaf that is smaller)."""
    import jax

    rng = np.random.default_rng([seed, 0x5A])
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        n = int(np.prod(leaf.shape))
        take = min(n, SAMPLE_PER_LEAF)
        out[jax.tree_util.keystr(path)] = np.sort(rng.choice(n, size=take, replace=False)) if take < n else np.arange(n)
    return out


def sample(tree: Any, indices: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The sampled entries of every leaf as float32 on the host."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        key = jax.tree_util.keystr(path)
        out[key] = np.asarray(jnp.take(leaf.reshape(-1), jnp.asarray(indices[key])), dtype=np.float32)
    return out


def grad_rel(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> Tuple[float, Dict[str, float]]:
    per_leaf = {}
    for key, ref in want.items():
        norm = float(np.linalg.norm(ref.astype(np.float64)))
        diff = float(np.linalg.norm(got[key].astype(np.float64) - ref.astype(np.float64)))
        per_leaf[key] = diff / norm if norm > 0 else (0.0 if diff == 0 else float("inf"))
    return float(np.sqrt(np.mean(np.square(list(per_leaf.values()))))), per_leaf


def sequence_by_sequence(reference, config: Dict[str, Any], weights: Any, batch: Dict[str, Any],
                         indices: Dict[str, np.ndarray], precision: str = "float32"):
    """The reference's loss and sampled gradient for `batch`, one sequence at
    a time, averaged as the batch's mean loss is.  Only the sample of each
    sequence's gradient is kept."""
    one = reference.one_sequence_fn(config, precision)
    n = batch["tokens"].shape[0]
    total_loss, total = 0.0, None
    for i in range(n):
        loss, grads = one(weights, batch["tokens"][i], batch["targets"][i])
        part = sample(grads, indices)
        del grads
        total_loss += float(loss) / n
        total = {k: v / n for k, v in part.items()} if total is None else {k: total[k] + v / n for k, v in part.items()}
    return total_loss, total


def against_reference(reference, config: Dict[str, Any], weights: Any, batch: Dict[str, Any],
                      loss, grads_sample: Dict[str, np.ndarray], indices: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """Runs the float32 reference on `batch` and compares.  `loss` and
    `grads_sample` are what is judged: the program's, or in the control
    another precision of the reference itself."""
    ref_loss, ref_sample = sequence_by_sequence(reference, config, weights, batch, indices)
    rel, per_leaf = grad_rel(grads_sample, ref_sample)
    loss = float(loss)
    out = {
        "loss": loss,
        "loss_reference": ref_loss,
        "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
        "grad_rel": rel,
        "grad_rel_limit": config["correct"]["grad_rel_limit"],
        "grad_rel_worst_leaf": max(per_leaf, key=per_leaf.get),
        "grad_rel_worst": max(per_leaf.values()),
    }
    out["ok"] = bool(np.isfinite(loss) and out["grad_rel"] <= out["grad_rel_limit"])
    return out


def mean_of_locals(avg: Dict[str, np.ndarray], locals_: Any, limit: float) -> Dict[str, Any]:
    """The averaged gradient against the float32 mean of the groups' local
    ones: max over leaves of |avg - mean| / max|mean| (as
    `chip_smoke.compare_to_mean`)."""
    worst = 0.0
    for key, got in avg.items():
        mean = np.mean(np.stack([l[key].astype(np.float32) for l in locals_]), axis=0)
        scale = float(np.max(np.abs(mean))) or 1.0
        worst = max(worst, float(np.max(np.abs(got.astype(np.float32) - mean))) / scale)
    return {"avg_vs_mean": worst, "avg_vs_mean_limit": limit, "ok": bool(worst <= limit)}
