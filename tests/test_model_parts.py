"""The parts of the gradient program, by name.

The model writes `jax.named_scope`s (one vocabulary, `obs/spans.PARTS`) around
its forward computation; JAX's transforms carry them into the backward pass and
into what `jax.checkpoint` computes again; `TrainStep.op_map` reads them back
out of the compiled program, instruction by instruction.  Checked here for a
small model of each of the benchmark's five configurations, on the CPU: every
instruction that does work maps to a part, the parts and directions are the
architecture's, and the scopes change no instruction.
"""

import contextlib
import dataclasses
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from hlo_text import canonical, without_metadata  # noqa: E402
from torchft_tpu.models import LayerKind, TransformerConfig, init_params  # noqa: E402
from torchft_tpu.models.mixer import Mixer  # noqa: E402
from torchft_tpu.models.mixers import MIXERS  # noqa: E402
from torchft_tpu.models.transformer import loss_and_counters, param_axes  # noqa: E402
from torchft_tpu.obs import opmap  # noqa: E402
from torchft_tpu.obs.spans import PARTS  # noqa: E402
from torchft_tpu.parallel import TrainStep, ft_init_mesh  # noqa: E402

SEQ = 64
_BASE = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128, max_seq=SEQ,
             dtype=jnp.float32, remat=False, scan_unroll=4)
# The five configurations' shapes in small: what each has that the others lack.
MODELS = {
    "internlm2": TransformerConfig(**_BASE),
    "mistral": TransformerConfig(**dict(_BASE, d_model=128, n_heads=8, d_ff=448, vocab_size=128)),
    "olmoe": TransformerConfig(**dict(_BASE, n_kv_heads=4, qk_norm=True, moe_experts=8, moe_top_k=2, d_ff=64,
                                      moe_capacity_factor=None, moe_norm_topk=False, moe_z_coef=0.001)),
    "moonlight": TransformerConfig(**dict(
        _BASE, n_layers=3, n_kv_heads=4, mla_kv_rank=32, mla_nope_dim=16, mla_rope_dim=8, mla_v_dim=16,
        moe_experts=8, moe_top_k=2, d_ff=32, moe_capacity_factor=None, moe_held=(2, 2), moe_score="sigmoid",
        moe_route_scale=2.446, moe_shared_experts=2, moe_aux_coef=0.001, moe_dense_layers=1, dense_d_ff=128,
        remat=True, remat_keeps_attention=True)),
    "keye": TransformerConfig(**dict(
        _BASE, head_dim=32, qk_norm_per_head=True, dsa_index_heads=3, dsa_index_dim=16, dsa_topk=16,
        moe_experts=8, moe_top_k=2, d_ff=48, moe_capacity_factor=None, moe_held=(2, 2), moe_aux_coef=0.001,
        remat=True, remat_keeps_attention=True)),
}
# The five that the benchmark had before a layer pattern was data: their compiled programs are pinned
# (`test_the_pattern_left_the_five_programs_as_they_were`).
BEFORE_THE_PATTERN = tuple(MODELS)
# The sixth: window and full attention mixed 3:1 at two head counts, YaRN on half a head, a head gate,
# three kinds of layer under three stacks.
_FULL = dict(n_heads=6, rope_theta=5e5, rotary_fraction=0.5, yarn=(4.0, 16, 4.0, 1.0, 1.1))
MODELS["laguna"] = TransformerConfig(**dict(
    _BASE, n_layers=5, n_heads=6, n_kv_heads=2, head_dim=16, attn_head_gate=True, moe_experts=8, moe_top_k=2, d_ff=32,
    dense_d_ff=128, moe_capacity_factor=None, moe_held=(2, 2), moe_score="sigmoid", moe_route_scale=2.5,
    moe_shared_experts=1, moe_aux_coef=0.001, remat=True, remat_keeps_attention=True, scan_unroll=8,
    pattern=(LayerKind("dense_layers", False, **_FULL),) + (LayerKind("window_layers", True, 8, 1e4, window=16),) * 3 + (LayerKind("layers", True, **_FULL),)))
# The six the benchmark had before a mixer was the kind's: PR 41 pins the sixth beside the five.
PINNED = BEFORE_THE_PATTERN + ("laguna",)
# The seventh: attention inside a compressed latent, a router with a carried state and a choice that
# takes no expert, learned merges, the head read off the embedding.
MODELS["zaya"] = TransformerConfig(**dict(
    _BASE, n_layers=3, n_heads=8, n_kv_heads=2, head_dim=16, moe_experts=8, moe_top_k=1, moe_norm_topk=False, d_ff=32,
    moe_capacity_factor=None, moe_held=(0, 4), moe_router_state=16, moe_skip=True, scaled_merge=True, tied_head=True,
    remat=True, remat_keeps_attention=True, scan_unroll=8,
    pattern=(LayerKind("layers", True, 8, 5e6, rotary_fraction=0.5, mixer="cca"),) * 3))
# The seven the benchmark had before latent attention was a kind's and the walk gave a choice bias's rows by the
# layer's place among the sparse layers: PR 48 pins the seventh beside the six.
PINNED = PINNED + ("zaya",)
# The eighth: Kimi Delta Attention 3 : 1 with unrotated latent attention, a dense first layer, three stacks of
# which two are sparse (layers 1-5 of the published pattern: the benchmark's cut).
_KDA, _NOPE = dict(rope_theta=1e4, rotary_fraction=0.0, mixer="kda"), dict(rope_theta=1e4, rotary_fraction=0.0, mixer="mla")
MODELS["kimi"] = TransformerConfig(**dict(
    _BASE, n_layers=5, n_heads=2, n_kv_heads=2, kda_head_dim=16, mla_kv_rank=32, mla_nope_dim=16, mla_rope_dim=8,
    mla_v_dim=16, moe_experts=8, moe_top_k=2, d_ff=32, dense_d_ff=128, moe_capacity_factor=None, moe_held=(2, 2),
    moe_score="sigmoid", moe_route_scale=2.446, moe_shared_experts=1, moe_aux_coef=0.001, remat=True,
    remat_keeps_attention=True, scan_unroll=8,
    pattern=(LayerKind("kda_dense", False, 2, **_KDA),) + (LayerKind("kda_layers", True, 2, **_KDA),) * 2
    + (LayerKind("mla_layers", True, 2, **_NOPE), LayerKind("kda_layers", True, 2, **_KDA))))
# The ninth: a router that reads the layer's input before attention, ReGLU experts under a softmax over the kept,
# window layers under RoPE 3 : 1 with un-rotated full layers at seven query heads a KV head, two whole periods.
MODELS["smallthinker"] = TransformerConfig(**dict(
    _BASE, n_layers=8, n_heads=7, n_kv_heads=1, head_dim=16, moe_experts=8, moe_top_k=3, d_ff=32,
    moe_capacity_factor=None, moe_held=(2, 2), moe_aux_coef=0.0, moe_router_early=True, moe_activation="relu",
    remat=True, remat_keeps_attention=True, scan_unroll=8,
    pattern=(LayerKind("layers", True, 7, 1.5e6, rotary_fraction=0.0),
             *(LayerKind("window_layers", True, 7, 1.5e6, window=16),) * 3) * 2))
# The tenth: blocks that are ONE norm and ONE part — a Mamba-2 mixer, un-rotated attention at a group of two, un-gated
# ReLU^2 experts beside a shared one — three stacks of unequal leaf sets, the published pattern's first nine letters.
_M = LayerKind("mamba", False, 4, 1e4, rotary_fraction=0.0, mixer="mamba2", feed_forward=False)
_A = LayerKind("attn", False, 4, 1e4, rotary_fraction=0.0, feed_forward=False)
_E = LayerKind("moe", True, 4, 1e4, rotary_fraction=0.0, mixer="none")
MODELS["nemotron"] = TransformerConfig(**dict(
    _BASE, n_layers=9, n_heads=4, n_kv_heads=2, head_dim=16, moe_experts=8, moe_top_k=2, d_ff=24,
    moe_capacity_factor=None, moe_held=(2, 2), moe_score="sigmoid", moe_route_scale=2.5, moe_shared_experts=2,
    moe_aux_coef=0.0, moe_activation="relu2", ssm_head_dim=8, ssm_groups=2, ssm_state=16, ssm_chunk=16,
    remat=True, remat_keeps_attention=True, scan_unroll=16, pattern=(_M, _E, _M, _E, _M, _A, _E, _M, _E)))
# The ten the benchmark had before a mixer was an entry of a table (`models/mixers.MIXERS`): PR 61 pins the eighth to
# tenth beside the seven, from its parent tree.
PINNED = PINNED + ("kimi", "smallthinker", "nemotron")
# The eleventh: two dense blocks of four norms each run three times over the same weights, a head and an exit gate after
# every pass, the exit-weighted loss (Ouro's shape).  Pinned by PR 63, which brought it: a later change to the walk, the
# passes or the per-row head is meant to leave this program alone, or to record it anew.
MODELS["ouro"] = TransformerConfig(**dict(
    _BASE, n_heads=4, n_kv_heads=4, head_dim=16, remat=True, scan_unroll=8, loop_steps=3, exit_beta=0.05,
    pattern=(LayerKind("layers", False, 4, 1e6, post_norms=True),) * 2))
PINNED = PINNED + ("ouro",)
# The twelfth: Keye's body without its indexer under the block-diffusion objective — a noised and a clean copy of every
# sequence in one stream of 2 x 64 positions in blocks of 4, the three-part mask, the head over the noised half under a
# weight a row (SDAR's shape).  Pinned by PR 66, which brought it.
MODELS["sdar"] = TransformerConfig(**dict(
    _BASE, head_dim=32, qk_norm_per_head=True, moe_experts=8, moe_top_k=2, d_ff=48, moe_capacity_factor=None,
    moe_held=(2, 2), moe_aux_coef=0.001, remat=True, remat_keeps_attention=True, rope_theta=1e6, bd_block_length=4,
    bd_noise_seed=66))
PINNED = PINNED + ("sdar",)
# The thirteenth: Gated DeltaNet — a decay a head, two key heads under four value heads — 3 : 1 with attention under a
# gate a column, a quarter of a head rotated, zero-centred norms, a shared expert under its own gate (Qwen3-Next's
# shape: one whole period).  Pinned by PR 68, which brought it.
MODELS["qwen3next"] = TransformerConfig(**dict(
    _BASE, n_layers=4, head_dim=32, qk_norm_per_head=True, attn_out_gate=True, norm_unit_offset=True, gdn_key_heads=2,
    gdn_key_dim=16, gdn_value_dim=16, moe_experts=8, moe_top_k=3, d_ff=32, moe_capacity_factor=None, moe_held=(2, 2),
    moe_shared_experts=1, moe_shared_gate=True, moe_aux_coef=0.001, remat=True, remat_keeps_attention=True, scan_unroll=8,
    pattern=(LayerKind("gdn_layers", True, 4, 1e7, rotary_fraction=0.25, mixer="gdn"),) * 3
    + (LayerKind("attn_layers", True, 4, 1e7, rotary_fraction=0.25),)))
PINNED = PINNED + ("qwen3next",)
# Instructions that do the device's work (a copy, a bitcast or a tuple moves or names data).
HEAVY = ("dot", "convolution", "fusion", "custom-call")


def _step_and_arguments(name: str):
    cfg = MODELS[name]
    biased = (cfg.moe_score == "sigmoid" and (not cfg.pattern or name in ("kimi", "nemotron"))) or cfg.moe_skip
    bias = jnp.zeros((cfg.n_sparse_layers, cfg.n_router_outputs), jnp.float32) if biased else None
    step = TrainStep(ft_init_mesh({"data": 1}, devices=jax.devices()[:1]), optax.adamw(1e-3),
                     lambda p, b: loss_and_counters(p, b, cfg, router_bias=bias), loss_has_counters=True)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, size=(2, SEQ)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(np.roll(tokens, -1, axis=1))}
    return step, init_params(jax.random.PRNGKey(0), cfg), batch


@pytest.fixture(scope="module")
def programs():
    """Per model, compiled once: the gradient program's detailed op map (after
    one run of it) and its optimized text."""
    found = {}

    def get(name: str):
        if name not in found:
            step, params, batch = _step_and_arguments(name)
            loss, _ = step.grads(params, batch)
            assert np.isfinite(float(loss))
            found[name] = (step.op_map(detail=True)["jit_value_and_grad"],
                           step.lower_grads(params, batch).compile().as_text())
        return found[name]

    return get


@pytest.mark.parametrize("name", list(MODELS))
def test_every_instruction_that_does_work_has_a_part(programs, name) -> None:
    ops, _ = programs(name)
    heavy = {k: v for k, v in ops.items() if v["opcode"] in HEAVY}
    assert len(heavy) > 10, sorted(ops)
    nameless = {k: v for k, v in heavy.items() if opmap.booked(v)[0] is None}
    # What is left carries no op_name at all, its own or inside it: XLA:CPU's own making (the first
    # half of a reduction it split in two, a slice or a broadcast it wrapped), and little of it
    assert all(v["op_name"] == "" and not v["inside"] for v in nameless.values()), nameless
    assert len(nameless) <= len(heavy) // 5, (len(nameless), len(heavy))


@pytest.mark.parametrize("name", list(MODELS))
def test_parts_and_directions_are_the_architectures(programs, name) -> None:
    cfg, (ops, _) = MODELS[name], programs(name)
    named = [opmap.booked(v) for v in ops.values()]
    parts = {p for p, _ in named if p is not None}
    assert parts <= set(PARTS)
    expected = {"embed", "norm", "attn_proj", "attn", "head_loss", "stack"}
    if cfg.moe_experts:
        expected |= {"router", "experts"}
    if any(kind.feed_forward and not kind.sparse for kind in cfg.layers):
        expected |= {"ffn"}
    if any(kind.mixer == "mamba2" for kind in cfg.layers):
        expected |= {"ssm_mix", "ssm_scan"}
    if any(kind.window for kind in cfg.layers):
        expected |= {"attn_window"}
    if any(kind.mixer == "cca" for kind in cfg.layers):
        expected |= {"cca_mix"}
    if any(kind.mixer == "kda" for kind in cfg.layers):
        expected |= {"kda_mix", "kda_scan"}
    if any(kind.mixer == "gdn" for kind in cfg.layers):
        expected |= {"gdn_mix", "gdn_scan"}
    if cfg.moe_shared_experts:
        expected |= {"shared_expert"}
    if cfg.dsa_index_heads:
        expected |= {"dsa_index", "dsa_select"}
    if cfg.exit_beta is not None:
        expected |= {"exit_gate"}
    if cfg.bd_block_length is not None:  # the attention call over the doubled stream has a name of its own
        expected = expected - {"attn"} | {"bd_noise", "bd_attn"}
    assert parts == expected
    directions = {d for p, d in named if p is not None}
    assert directions == ({"fwd", "bwd", "recompute"} if cfg.remat else {"fwd", "bwd"})
    # the head and the loss are outside the rematerialised layers; a layer's products are inside
    assert ("head_loss", "recompute") not in named
    if cfg.remat:
        assert ("attn_proj", "recompute") in named and ("experts" if cfg.moe_experts else "ffn", "recompute") in named


@pytest.mark.parametrize("name", list(MODELS))
def test_the_scopes_change_no_instruction(programs, name) -> None:
    """The same program built with `jax.named_scope` doing nothing (patched
    here, in the test: the source has no switch) compiles to the same
    optimized HLO once the metadata is gone."""
    _, scoped = programs(name)
    assert 'op_name="jit(value_and_grad)/jvp(embed)' in scoped
    with mock.patch.object(jax, "named_scope", lambda _name: contextlib.nullcontext()):
        step, params, batch = _step_and_arguments(name)
        plain = step.lower_grads(params, batch).compile().as_text()
    assert "jvp(embed)" not in plain and "attn_proj" not in plain
    assert without_metadata(plain) == without_metadata(scoped)
    assert canonical(plain) == canonical(scoped)


def _first_of(name: str, part: str) -> int:
    """The place, among the forward pass's equations in the order they were
    traced, of the first one whose innermost scope is `part`."""
    step, params, batch = _step_and_arguments(name)
    cfg = MODELS[name]
    jaxpr = jax.make_jaxpr(lambda p, b: loss_and_counters(p, b, dataclasses.replace(cfg, remat=False))[0])(params, batch)
    for i, eqn in enumerate(jaxpr.jaxpr.eqns):
        scopes = [word for word in str(eqn.source_info.name_stack).split("/") if word in PARTS]
        if scopes and scopes[-1] == part:
            return i
    raise AssertionError(f"no equation of {part} in {name}")


@pytest.mark.parametrize("name,early", [("smallthinker", True), ("laguna", False), ("olmoe", False)])
def test_an_early_router_stands_before_attention(name, early) -> None:
    """`moe_router_early`: the part `router` — scores from the layer's input,
    the choice, the gates — is traced before the layer's attention, its
    projections included; every other model routes after it."""
    router = _first_of(name, "router")
    assert (router < _first_of(name, "norm")) == early  # the layer's first norm: the early router reads the raw stream
    assert (router < _first_of(name, "attn")) == early
    assert router < _first_of(name, "experts")


_RECORDED = os.path.join(ROOT, "tests", "data", "hlo_before_the_pattern.json")


def _digest(step, params, batch, program: str, grads_text=None) -> str:
    """`grads_text`: the gradient program's compiled text where the caller
    holds it already (the `programs` fixture), else it is compiled here."""
    import hashlib

    if program == "grads":
        text = grads_text or step.lower_grads(params, batch).compile().as_text()
    else:  # the update program takes gradients of the parameters' own shapes and types: they stand in
        text = step._apply_fn.lower(params, step.init_opt_state(params), params).compile().as_text()
    return hashlib.sha256(canonical(text).encode()).hexdigest()


def record(commit: str) -> None:
    """Records the twenty-six digests anew (`python tests/test_model_parts.py "<commit and why>"`,
    `JAX_PLATFORMS=cpu`): for a PR that changes the ten gradient programs on
    purpose.  The update programs are no model code's to change, so theirs
    have to come out as they were."""
    import json

    with open(_RECORDED, encoding="utf-8") as f:
        before = json.load(f)
    digests = {f"{name}.{program}": _digest(*_step_and_arguments(name), program)
               for name in PINNED for program in ("grads", "update")}
    if before["jax"] == jax.__version__:
        recorded = before["sha256_of_canonical_hlo"]  # a model pinned for the first time has no digest to differ from
        moved = sorted(k for k, v in digests.items() if k in recorded and v != recorded[k])
        assert not [k for k in moved if k.endswith(".update")], moved
        print("differ from the record:", moved or "none")
    with open(_RECORDED, "w", encoding="utf-8") as f:
        json.dump({"jax": jax.__version__, "commit": commit, "sha256_of_canonical_hlo": digests}, f, indent=1)
        f.write("\n")


# The stacks of the models whose pattern names its own (the others: "layers", and "dense_layers" where some lead).
_STACKS = {"laguna": {"dense_layers", "window_layers", "layers"}, "kimi": {"kda_dense", "kda_layers", "mla_layers"},
           "smallthinker": {"layers", "window_layers"}, "nemotron": {"mamba", "attn", "moe"},
           "qwen3next": {"gdn_layers", "attn_layers"}}


@pytest.mark.parametrize("program", ["grads", "update"])
@pytest.mark.parametrize("name", PINNED)
def test_the_pattern_left_the_five_programs_as_they_were(programs, name, program) -> None:
    """`_decoder` walks a pattern since PR 37, of which "leading dense layers,
    then the model's own kind" is one instance: for the five configurations the
    benchmark had, the gradient and the update program compile to the
    instructions they compiled to before (canonical optimized HLO, by digest;
    recorded from the parent tree with this JAX) — and since PR 41, which made
    the mixer the kind's and let the walk carry a second stream, the sixth
    (window and full attention mixed) with them, since PR 48 the seventh and
    since PR 61, which made a mixer an entry of `models/mixers.MIXERS`, all ten
    (each recorded from the tree BEFORE the change it guards); the eleventh, a
    looped model, the twelfth, one trained by block diffusion, and the
    thirteenth, Gated DeltaNet with gated attention, from the PRs that brought
    them (63, 66, 68).  A PR that
    changes these programs on purpose records them anew:
    `tests/data/hlo_before_the_pattern.json`."""
    import json

    with open(_RECORDED, encoding="utf-8") as f:
        recorded = json.load(f)
    if recorded["jax"] != jax.__version__:
        pytest.skip(f"recorded with JAX {recorded['jax']}, this is {jax.__version__}")
    step, params, batch = _step_and_arguments(name)
    grads_text = programs(name)[1] if program == "grads" else None  # compiled once a model for this module's tests
    assert _digest(step, params, batch, program, grads_text) == recorded["sha256_of_canonical_hlo"][f"{name}.{program}"]
    # and the tree keeps its leaves' names and shapes: heal and checkpoints read what they wrote
    cfg = MODELS[name]
    assert set(cfg.stacks) == _STACKS.get(name, {"layers"} | ({"dense_layers"} if cfg.moe_dense_layers else set()))
    assert set(params) == ({"embed", "final_norm"} | set(cfg.stacks) | (set() if cfg.tied_head else {"lm_head"})
                           | ({"exit_gate"} if cfg.exit_beta is not None else set()))
    # a stack holds a row a layer of its first norm: the mixer's, or the feed-forward's where the block has no mixer
    assert [name for name, (kind, _) in cfg.stacks.items() if "attn_norm" not in params[name]] == (
        ["moe"] if name == "nemotron" else [])
    assert {s: n for s, (_, n) in cfg.stacks.items()} == {
        s: params[s]["attn_norm" if "attn_norm" in params[s] else "mlp_norm"].shape[0] for s in cfg.stacks}


# -- the seam: a mixer is an entry of `MIXERS`, and the model asks the entry


def _is_axes(x) -> bool:
    return isinstance(x, tuple)


@pytest.mark.parametrize("mixer,model", [("attention", "keye"), ("mla", "moonlight"), ("cca", "zaya"), ("kda", "kimi"),
                                         ("mamba2", "nemotron"), ("gdn", "qwen3next")])
def test_a_mixer_lists_its_own_leaves(mixer, model) -> None:
    """`Mixer.axes` and `Mixer.init` name exactly the same leaves — the model
    adds nothing for all and drops nothing for some — and every leaf is a row
    a layer of as many axes as its axes' names."""
    assert set(MIXERS) == {"attention", "mla", "cca", "kda", "gdn", "mamba2"}
    cfg = MODELS[model]
    kind = next(kind for kind in cfg.layers if kind.mixer == mixer)
    entry = MIXERS[mixer]
    axes = entry.axes(cfg, kind)
    leaves = jax.eval_shape(lambda: entry.init(jax.random.PRNGKey(0), cfg, 3, kind))
    assert set(axes) == set(leaves) and "attn_norm" not in leaves and "mlp_norm" not in leaves
    for name, leaf in leaves.items():
        assert leaf.shape[0] == 3 and axes[name][0] == "layers" and len(axes[name]) == len(leaf.shape), name
    assert (entry.check is None) == (mixer == "attention")  # the plain heads refuse nothing
    if entry.check is not None:
        entry.check(cfg, kind)


@pytest.mark.parametrize("name", list(MODELS))
def test_the_axes_and_the_leaves_are_one_tree(name) -> None:
    cfg = MODELS[name]
    axes, leaves = param_axes(cfg), jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(axes, is_leaf=_is_axes) == jax.tree.structure(leaves)
    assert all(len(a) == len(leaf.shape) for a, leaf in zip(jax.tree.leaves(axes, is_leaf=_is_axes), jax.tree.leaves(leaves)))


def _batch(cfg):
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, size=(2, SEQ)).astype(np.int32)
    return {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(np.roll(tokens, -1, axis=1))}


def _listening(monkeypatch, name: str, statistic: str) -> list:
    """`MIXERS[name]` with a forward that also notes down each layer's `statistic`."""
    entry, heard = MIXERS[name], []

    def forward(*args):
        y, stats = entry.forward(*args)
        heard.append(stats[statistic])
        return y, stats

    monkeypatch.setitem(MIXERS, name, dataclasses.replace(entry, forward=forward))
    return heard


def test_a_sixth_mixer_costs_no_edit_of_the_model(monkeypatch) -> None:
    """An entry put into `MIXERS` from outside — one projection, one statistic
    of its own, one kept name — and a pattern that names it: the tree, the
    gradient, the counter and `remat_keeps_attention` all go by the entry."""
    from jax.ad_checkpoint import checkpoint_name

    def forward(cfg, kind, mesh, rules, h, w, positions):
        with jax.named_scope("attn_proj"):
            y = checkpoint_name(jnp.tanh(h @ w["toy_w"].astype(cfg.dtype)), "toy_out")
        return y, {"toy_gain": jnp.mean(jnp.abs(y))}

    def check(cfg, kind) -> None:
        assert kind.n_heads == 1, "the toy has one head"

    monkeypatch.setitem(MIXERS, "toy", Mixer(
        init=lambda key, cfg, L, kind: {"toy_w": jax.random.normal(jax.random.fold_in(key, 9), (L, cfg.d_model, cfg.d_model)) * 0.1},
        axes=lambda cfg, kind: {"toy_w": ("layers", "embed", None)},
        forward=forward, saved_names=("toy_out",), mean_statistic=("toy_gain", "toy_gain_mean"), check=check))
    toy = LayerKind("toy_layers", True, 1, 1e4, mixer="toy")
    sizes = dict(_BASE, n_layers=3, moe_experts=4, moe_top_k=2, d_ff=32, moe_capacity_factor=None)
    with pytest.raises(AssertionError, match="the toy has one head"):
        TransformerConfig(**dict(sizes, pattern=(dataclasses.replace(toy, n_heads=2),) * 3))
    with pytest.raises(AssertionError, match="a kind's mixer is one of"):
        TransformerConfig(**dict(sizes, pattern=(dataclasses.replace(toy, mixer="no_such"),) * 3))
    cfg = TransformerConfig(**dict(sizes, pattern=(toy, LayerKind("layers", True, 4, 1e4), toy)))
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert set(params["toy_layers"]) == {"attn_norm", "toy_w", "mlp_norm", "router", "w_gate", "w_up", "w_down"}
    assert params["toy_layers"]["toy_w"].shape == (2, 64, 64) and "toy_w" not in params["layers"]
    assert jax.tree.structure(param_axes(cfg), is_leaf=_is_axes) == jax.tree.structure(params)
    batch = _batch(cfg)
    heard = _listening(monkeypatch, "toy", "toy_gain")
    _, counters = loss_and_counters(params, batch, cfg)  # eagerly: what is noted down are values
    heard = [float(a) for a in heard]
    assert len(heard) == 2 and heard[0] != heard[1]
    np.testing.assert_allclose(float(counters["toy_gain_mean"]), np.mean(heard), rtol=1e-6)
    assert "kda_alpha_mean" not in counters and "ssm_decay_mean" not in counters
    grads = jax.grad(lambda p: loss_and_counters(p, batch, cfg)[0])(params)
    assert np.isfinite(np.asarray(grads["toy_layers"]["toy_w"])).all()
    assert all(float(jnp.abs(g).max()) > 0 for g in grads["toy_layers"]["toy_w"])  # each of the two layers learns

    def made(keeps: bool) -> int:
        """How often the gradient program makes the toy's named output."""
        remat = dataclasses.replace(cfg, remat=True, remat_keeps_attention=keeps)
        return str(jax.make_jaxpr(jax.grad(lambda p: loss_and_counters(p, batch, remat)[0]))(params)).count("name=toy_out")

    # kept by the name the entry lists: the backward pass reads each toy layer's output, where without it makes it again
    assert (made(True), made(False)) == (2, 4)


def test_two_recurrent_mixers_in_one_pattern_count_a_mean_each(monkeypatch) -> None:
    """Kimi Delta Attention and Mamba-2 layers in one pattern: each decay's
    mean is counted by name, over the layers of its own mixer."""
    kda = LayerKind("kda_layers", True, 2, **_KDA)
    ssm = LayerKind("mamba", True, 4, 1e4, rotary_fraction=0.0, mixer="mamba2")
    cfg = TransformerConfig(**dict(
        _BASE, n_layers=5, n_heads=2, n_kv_heads=2, kda_head_dim=16, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
        ssm_chunk=16, moe_experts=4, moe_top_k=2, d_ff=32, moe_capacity_factor=None, scan_unroll=8,
        pattern=(kda, ssm, kda, kda, ssm)))
    params, batch = init_params(jax.random.PRNGKey(0), cfg), _batch(cfg)
    alphas, decays = _listening(monkeypatch, "kda", "kda_alpha"), _listening(monkeypatch, "mamba2", "ssm_decay")
    _, counters = loss_and_counters(params, batch, cfg)  # eagerly: what is noted down are values
    alphas, decays = [float(a) for a in alphas], [float(a) for a in decays]
    assert (len(alphas), len(decays)) == (3, 2)
    np.testing.assert_allclose(float(counters["kda_alpha_mean"]), np.mean(alphas), rtol=1e-6)
    np.testing.assert_allclose(float(counters["ssm_decay_mean"]), np.mean(decays), rtol=1e-6)
    assert abs(np.mean(alphas) - np.mean(decays)) > 1e-3  # two means, not one sum shared
    loss, _ = jax.jit(lambda p: loss_and_counters(p, batch, dataclasses.replace(cfg, scan_unroll=1)))(params)
    assert np.isfinite(float(loss))


def test_the_op_map_names_both_programs_and_costs_nothing_until_asked(monkeypatch) -> None:
    """Set-up and steps never lower, compile or read a program's text for the
    map (counted here); asking does, for the programs that last ran."""
    from jax._src import stages

    calls = {"lower": 0, "compile": 0, "as_text": 0}

    def counting(cls, attr, key):
        real = getattr(cls, attr)

        def wrapper(self, *a, **k):
            calls[key] += 1
            return real(self, *a, **k)

        monkeypatch.setattr(cls, attr, wrapper)

    counting(stages.Lowered, "compile", "compile")
    counting(stages.Compiled, "as_text", "as_text")
    lowered = []
    step, params, batch = _step_and_arguments("internlm2")
    for fn in ("_grads_fn", "_apply_fn", "_apply_spec_fn"):
        jitted = getattr(step, fn)

        class Counted:
            def __init__(self, inner):
                self.inner, self.__name__ = inner, inner.__name__

            def __call__(self, *a, **k):
                return self.inner(*a, **k)

            def lower(self, *a, **k):
                calls["lower"] += 1
                lowered.append(self.__name__)
                return self.inner.lower(*a, **k)

        setattr(step, fn, Counted(jitted))
    opt = step.init_opt_state(params)
    for _ in range(3):
        loss, grads = step.grads(params, batch)
        params, opt = step.apply(params, opt, grads)
    assert calls == {"lower": 0, "compile": 0, "as_text": 0}
    assert step in opmap.train_steps()
    found = step.op_map()
    assert calls == {"lower": 2, "compile": 2, "as_text": 2} and sorted(lowered) == ["apply", "value_and_grad"]
    assert set(found) == {"jit_value_and_grad", "jit_apply"}
    assert all(isinstance(v, str) for program in found.values() for v in program.values())
    grad_parts = {opmap.part_of(v) for v in found["jit_value_and_grad"].values()}
    assert {"ffn", "head_loss", "attn"} <= grad_parts
    assert {opmap.part_of(v) for v in found["jit_apply"].values()} == {None}  # the optimizer is no part of the model


@pytest.mark.parametrize("then", ["asked_again", "traced_anew"])
def test_the_compiled_texts_are_read_once_for_the_programs_that_ran(then, monkeypatch) -> None:
    """`compiled_texts` is what `op_map` reads: the programs' text is made
    once, handed out again without another compile, and made anew after a
    program was traced anew (another batch shape)."""
    from jax._src import stages

    compiles = []
    real = stages.Lowered.compile
    monkeypatch.setattr(stages.Lowered, "compile", lambda self, *a, **k: compiles.append(1) or real(self, *a, **k))
    step, params, batch = _step_and_arguments("internlm2")
    step.grads(params, batch)
    texts = step.compiled_texts()
    assert set(texts) == {"jit_value_and_grad"} and len(compiles) == 1
    assert step.op_map() == {"jit_value_and_grad": opmap.op_names(texts["jit_value_and_grad"])} and len(compiles) == 1
    if then == "asked_again":
        assert step.compiled_texts() is texts and len(compiles) == 1
    else:
        step.grads(params, {k: v[:, : SEQ // 2] for k, v in batch.items()})
        again = step.compiled_texts()
        assert len(compiles) == 2 and again["jit_value_and_grad"] != texts["jit_value_and_grad"]


def test_a_train_step_that_is_gone_leaves_the_registry() -> None:
    import gc

    step, _, _ = _step_and_arguments("internlm2")
    assert step in opmap.train_steps()
    ident = id(step)
    del step
    gc.collect()
    assert ident not in {id(s) for s in opmap.train_steps()}


@pytest.mark.parametrize("op_name,part,direction", [
    ("jit(value_and_grad)/jvp(ffn)/dot_general", "ffn", "fwd"),
    ("jit(value_and_grad)/transpose(jvp(attn_proj))/dot_general", "attn_proj", "bwd"),
    ("jit(value_and_grad)/transpose(jvp(jvp()))/checkpoint/rematted_computation/attn_proj/norm/mul", "norm", "recompute"),
    ("jit(value_and_grad)/transpose(jvp(jvp()))/checkpoint/experts/tpuft_gmm_dlhs/pallas_call", "experts", "bwd"),
    ("jit(value_and_grad)/jvp(attn_proj/norm)/jit(norm)/mul", "norm", "fwd"),
    ("jit(value_and_grad)/jvp(head_loss)/jit(stack)/transpose", "head_loss", "fwd"),
    ("jit(value_and_grad)/jvp()/transpose", None, "fwd"),
    ("params['embed']", None, "fwd"),
    ("", None, "fwd"),
])
def test_an_op_name_is_classified_by_its_innermost_scope(op_name, part, direction) -> None:
    assert (opmap.part_of(op_name), opmap.direction_of(op_name)) == (part, direction)


def test_the_text_reader_takes_what_runs_and_leaves_what_is_fused() -> None:
    text = """HloModule jit_value_and_grad, is_scheduled=true

FileNames
1 "model.py"

%fused_computation.3 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %inner.1 = f32[8]{0} tanh(%p.1), metadata={op_name="jit(f)/jvp(norm)/tanh"}
  ROOT %inner.2 = f32[8]{0} multiply(%inner.1, %p.1), metadata={op_name="jit(f)/jvp(ffn)/mul"}
}

%add.red (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum.9 = f32[] add(%a, %b)
}

%body.1 (s: (f32[8], s32[])) -> (f32[8], s32[]) {
  %s = (f32[8]{0}, s32[]) parameter(0)
  %in_loop.1 = f32[8]{0} get-tuple-element(%s), index=0
  ROOT %looped.2 = (f32[8]{0}, s32[]) tuple(%in_loop.1, %in_loop.1), metadata={op_name="jit(f)/jvp(stack)/while/body/add"}
}

%cond.1 (s.1: (f32[8], s32[])) -> pred[] {
  %s.1 = (f32[8]{0}, s32[]) parameter(0)
  ROOT %less.1 = pred[] constant(false)
}

ENTRY %main.7 (x: f32[8]) -> f32[] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.3 = f32[8]{0:T(8)S(1)} fusion(%x), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/jvp(ffn)/mul" stack_frame_id=4}
  %pair.1 = (f32[8]{0}, s32[]) tuple(%fusion.3, %fusion.3)
  %while.2 = (f32[8]{0}, s32[]) while(%pair.1), condition=%cond.1, body=%body.1
  %kernel.4 = (f32[8]{0}, f32[8]{0}) custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(attn)/tpuft_fa_fwd/pallas_call"}
  ROOT %reduce.5 = f32[] reduce(%fusion.3, %x), dimensions={0}, to_apply=%add.red, metadata={op_name="jit(f)/jvp(head_loss)/reduce_sum"}
}
"""
    assert opmap.module_name(text) == "jit_value_and_grad"
    flat = opmap.op_names(text)
    assert set(flat) == {"x", "fusion.3", "pair.1", "while.2", "kernel.4", "reduce.5", "s", "in_loop.1", "looped.2",
                         "s.1", "less.1"}
    assert flat["fusion.3"] == "jit(f)/jvp(ffn)/mul" and flat["pair.1"] == ""
    detail = opmap.op_names(text, detail=True)
    assert detail["kernel.4"] == {"op_name": "jit(f)/jvp(attn)/tpuft_fa_fwd/pallas_call", "opcode": "custom-call"}
    assert detail["fusion.3"]["opcode"] == "fusion"
    assert detail["fusion.3"]["inside"] == {"jit(f)/jvp(ffn)": 1, "jit(f)/jvp(norm)": 1}  # it straddles two parts
    assert opmap.booked(detail["fusion.3"]) == ("ffn", "fwd")  # and goes by its own name
    nameless = {"op_name": "", "opcode": "fusion", "inside": {"jit(f)/transpose(jvp(experts))": 2, "jit(f)/jvp(router)": 1}}
    assert opmap.booked(nameless) == ("experts", "bwd") and opmap.booked({"op_name": "", "opcode": "copy"}) == (None, "fwd")
    assert detail["while.2"]["opcode"] == "while" and detail["reduce.5"]["opcode"] == "reduce"
    # what has no op_name anywhere goes where the nearest reader of its result goes, else where its operands do
    assert detail["pair.1"] == {"op_name": "", "opcode": "tuple", "near": ["ffn", "fwd"]} and "near" not in detail["fusion.3"]
    assert opmap.booked(detail["pair.1"]) == ("ffn", "fwd") and opmap.booked(detail["while.2"]) == ("ffn", "fwd")
    assert detail["in_loop.1"]["near"] == ["stack", "fwd"] and "near" not in detail["less.1"]
    stripped = without_metadata(text)
    assert "metadata" not in stripped and "model.py" not in stripped and "%fusion.3 = " in stripped
    assert canonical(text) == canonical(text.replace("kernel.4", "jvp_kernel_.9"))


if __name__ == "__main__":
    record(sys.argv[1])
