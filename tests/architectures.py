"""The architecture suite: what every architecture of the benchmark's
``programs/`` is held to, written once.

An architecture is one ``Architecture`` entry, ``ARCH`` in its own file
(``tests/test_<architecture>.py``), beside the tests of what only it has.  That
file imports the tests below that its entry has fields for, and
``pytest_generate_tests`` turns the entry's lists into cases whose ids carry
the architecture.  What many cases read — the program's loss and gradients a
(configuration, variant, seed), the reference's, the uncut expert layer — is
computed once a process and kept (the driver runs a file on one worker, so an
architecture's cases share them).  ``docs/testing.md`` has the fields, the tests
each brings and the budget.
"""

import collections
import contextlib
import dataclasses
import functools
import json
import os
import sys
from typing import Any, Callable, Dict, Optional, Sequence, Tuple
from unittest.mock import MagicMock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from test_manager import make_manager, make_quorum, store  # noqa: F401 — `store` is the fixture the tests below take

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.spec import Benchmark  # noqa: E402
from torchft_tpu.models import init_params  # noqa: E402
from torchft_tpu.models.transformer import loss_and_counters, param_axes  # noqa: E402
from torchft_tpu.parallel import TrainStep, ft_init_mesh  # noqa: E402

BENCH = Benchmark(ROOT)

# A case of the comparison with the reference: the configuration by its name in
# `configs`, the program's variant by its name in `variants`, whether the two
# must agree (a variant that leaves out a piece of the mathematics must NOT),
# the one stack whose leaves the case asserts (None: all) and what a variant
# that must differ is read by ("leaves" | "loss").
Case = collections.namedtuple("Case", "id config variant seed agrees stack reading", defaults=(True, None, "leaves"))
# A piece of the mathematics left out: of the "reference" (`how` its `left_out`
# name; the program as published must differ from it) or of the "program"
# (`how`: (cfg, weights) -> (a context manager to trace under, cfg, weights);
# it must differ from the reference as published).
Piece = collections.namedtuple("Piece", "id side how")
# One chip's share of the expert layer, for `test_the_shares_add_up_to_the_uncut_layer`.
# `share(first, count, with_the_shared_expert, *inputs) -> (y, stats)`, `uncut(*inputs) -> (y, aux)`, `facts(stats, aux,
# the uncut layer's input gradients)` what the architecture asserts of the shares' counters beyond the common ones.
ExpertLayer = collections.namedtuple(
    "ExpertLayer", "inputs experts share uncut assignments sin atol grad_rtol grad_atol shared facts",
    defaults=(1.0, 1e-5, 1e-4, 1e-5, False, None))
# The tree that goes through `ft_step`, a heal's transport and the disk checkpoint.
Tiny = collections.namedtuple("Tiny", "params loss batch steps facts", defaults=(2, None))
# The walks under `remat` that an entry of the older form compares with its stored walk.
REMAT = {"remat": dict(remat=True, remat_keeps_attention=False),
         "remat_that_keeps_attention": dict(remat=True, remat_keeps_attention=True)}
HELD = ("every_expert_held", "a_share_of_the_experts")  # the two configurations of such an entry, by their names


@dataclasses.dataclass(eq=False)
class Architecture:
    name: str                                    # benchmark/programs/<name>.py and benchmark/reference/<name>.py
    configs: Dict[str, Dict[str, Any]]           # the small configurations, by the name the cases' ids carry
    sizes: str                                   # what each size of the small configuration is there for
    seq: int                                     # positions a sequence of the seeded batch (two sequences)
    variants: Dict[str, Any]                     # name -> fields of the program's TransformerConfig replaced, or a function of it
    leaf_cases: Sequence[Case] = ()
    leaf_error: str = "norm"                     # a leaf's error over its norm | "max": its largest entry's over the reference's
    leaf_tolerance: float = 3e-5
    loss_tolerance: float = 1e-6
    off_start: bool = False                      # every leaf moved off its start, so that biases and norm weights take part
    weights: Optional[Callable] = None           # (weights, variant or None) -> the weights both sides take
    weights_vary: Tuple[str, ...] = ()           # the variants whose weights are not the others'
    prune: Optional[Callable] = None             # (tree, variant) -> tree: the leaves a variant's program has
    counters: Optional[Callable] = None          # (counters, config) asserted where a case agrees
    tracing: Callable = contextlib.nullcontext   # what the program is traced under (a smaller chunk, say)
    stacks: Tuple[str, ...] = ()                 # the tree's top-level names, where cases go stack by stack
    remat: Optional[Tuple[str, int, Tuple[str, ...]]] = None   # (configuration, seed, walks) against the stored walk
    remat_ulps: int = 0                          # units in the loss's last place a rematerialised walk may differ by
    pieces: Sequence[Piece] = ()
    pieces_at: Optional[Tuple[str, int]] = None  # (configuration, seed) of the pieces' comparison
    piece_floor: float = 0.0                     # what a model without a piece must differ by, in `leaf_error`'s measure
    chips: Sequence[int] = ()                    # the counts of chips an expert layer is shared between
    expert_layer: Optional[Callable[[], ExpertLayer]] = None
    published: Optional[str] = None              # the benchmark's configuration
    tree_config: Optional[str] = None            # whose tree is compared with the reference's: a name in `configs`, or None: published
    tree_facts: Optional[Callable] = None        # (cfg, ours) asserted beyond "the reference's names and shapes"
    published_facts: Optional[Callable] = None   # (cfg, published configuration)
    refusals: Sequence[Tuple] = ()               # (id, changed keys, the message's pattern or None[, the reference refuses too])
    refusal_config: str = ""                     # the configuration the changed keys go into: a name in `configs`
    through: Sequence[str] = ()                  # of "ft_step", "heal", "disk_checkpoint"
    tiny: Optional[Callable[[], Tiny]] = None

    # Loaded once: `BENCH.reference` executes the file anew at every call, and the jitted functions it keeps with it.
    program = functools.cached_property(lambda self: BENCH.program(self.name))
    reference = functools.cached_property(lambda self: BENCH.reference(self.name))


def in_the_scan(omissions):
    """The variants that leave a piece out, each with its layers under
    `lax.scan` (`scan_unroll` 1): that a piece is missed does not rest on the
    walk, and a run of layers is then traced and compiled once, not a layer at
    a time.  `as_published` keeps the configuration's own walk."""
    def scanned(change):
        if callable(change):
            return lambda cfg: dataclasses.replace(change(cfg), scan_unroll=1)
        return dict(change, scan_unroll=1)

    return {name: change if name == "as_published" else scanned(change) for name, change in omissions.items()}


def omission_cases(omissions, seed: int, configs=HELD, read_by_loss=()):
    """A case a (variant, configuration): `as_published` agrees, every other
    variant leaves a piece of the mathematics out and must not."""
    return [Case(f"{name}-{config}", config, name, seed, name == "as_published", None, "loss" if name in read_by_loss else "leaves")
            for name in omissions for config in configs]


def equations(jaxpr, found=None) -> list:
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)  # a ClosedJaxpr holds one
                if hasattr(inner, "eqns"):
                    equations(inner, found)
    return found


def pytest_generate_tests(metafunc) -> None:
    """The module's `ARCH` and, where the test takes one, the entry's list
    under the argument's name: ids `<architecture>-<the case's own>`."""
    arch = getattr(metafunc.module, "ARCH", None)
    if arch is None or "arch" not in metafunc.fixturenames:
        return
    lists = {"case": arch.leaf_cases, "walk": arch.remat[2] if arch.remat else (), "piece": arch.pieces,
             "chips": arch.chips, "refusal": arch.refusals, "through": arch.through}
    taken = [name for name in lists if name in metafunc.fixturenames]
    if not taken:
        metafunc.parametrize("arch", [arch], ids=[arch.name])
        return
    (name,) = taken
    own = lambda v: str(getattr(v, "id", v[0] if isinstance(v, tuple) else v))  # noqa: E731
    metafunc.parametrize(("arch", name), [(arch, v) for v in lists[name]], ids=[f"{arch.name}-{own(v)}" for v in lists[name]])


# -- seeded inputs, and what is computed once ------------------------------------------------


def batch(seed: int, vocab: int, seq_len: int, sequences: int = 2):
    tokens = np.random.default_rng(seed).integers(0, vocab, size=(sequences, seq_len)).astype(np.int32)
    return {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(np.roll(tokens, -1, axis=1))}


def batches(vocab: int, seq_len: int):
    """`batch` at an entry's own vocabulary and length, either given anew by a test of its own."""
    return lambda seed, vocab=vocab, seq_len=seq_len: batch(seed, vocab, seq_len)


def off_start(weights, seed: int):
    """Every leaf moved off its start (a tenth of its spread, or 0.1 where it
    starts constant): biases, gates and the norms' weights then take part in
    every product."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda l: l + 0.1 * (float(jnp.std(l)) or 1.0) * jnp.asarray(rng.standard_normal(l.shape), jnp.float32), weights)


@functools.lru_cache(maxsize=None)
def inputs(arch: Architecture, config: str, seed: int, variant: Optional[str] = None):
    """(weights, batch) of a seed: the reference's weights, moved off their
    start where the entry says so, with a variant's change where it has one."""
    made = arch.reference.make_weights(seed, arch.configs[config])
    if arch.off_start:
        made = off_start(made, seed)
    if arch.weights is not None:
        made = arch.weights(made, variant)
    return made, batch(seed, arch.configs[config]["vocab_size"], arch.seq)


def _weights_key(arch, variant):
    """A variant that changes the weights has inputs (and a reference) of its own."""
    return variant if variant in arch.weights_vary else None


@contextlib.contextmanager
def patched(*changes):
    """(object or dict, name, value)s set while a program is traced."""
    with pytest.MonkeyPatch.context() as patch:
        for target, name, value in changes:
            (patch.setitem if isinstance(target, dict) else patch.setattr)(target, name, value)
        yield


def program_cfg(arch: Architecture, config: str, variant: str = "as_published"):
    """(the program's TransformerConfig, the router's bias or None) of a variant."""
    cfg = arch.program.transformer_config(arch.configs[config])
    change = arch.variants[variant]
    bias = jnp.asarray(arch.program.router_bias(arch.configs[config])) if hasattr(arch.program, "router_bias") else None
    if callable(change):
        return change(cfg), bias
    change = dict(change)
    if "router_bias" in change:
        bias = change.pop("router_bias")
    return dataclasses.replace(cfg, **change), bias


@functools.lru_cache(maxsize=None)
def program_fn(arch: Architecture, config: str, variant: str):
    """The jitted (weights, batch) -> ((loss, counters), gradients) of a
    variant: traced and compiled once, whatever the seed."""
    cfg, bias = program_cfg(arch, config, variant)
    return jax.jit(jax.value_and_grad(lambda p, b: loss_and_counters(p, b, cfg, router_bias=bias), has_aux=True))


@functools.lru_cache(maxsize=None)
def program_run(arch: Architecture, config: str, variant: str, seed: int):
    """(loss, counters, gradients) of the program's variant on the seed's inputs."""
    weights, data = inputs(arch, config, seed, _weights_key(arch, variant))
    if arch.prune is not None:
        weights = arch.prune(weights, variant)
    with arch.tracing():
        (loss, counters), grads = program_fn(arch, config, variant)(weights, data)
    return float(loss), counters, grads


@functools.lru_cache(maxsize=None)
def reference_run(arch: Architecture, config: str, seed: int, weights_of: Optional[str] = None, left_out: str = ""):
    """(loss, gradients) of the plain reference on the seed's inputs, a
    sequence at a time; `left_out`: without that piece of the mathematics."""
    weights, data = inputs(arch, config, seed, weights_of)
    one = arch.reference.one_sequence_fn(arch.configs[config], "float32", *([left_out] if left_out else []))
    runs = [one(weights, tokens, targets) for tokens, targets in zip(data["tokens"], data["targets"])]
    mean = lambda *leaves: sum(leaves) / len(runs)  # noqa: E731
    return float(mean(*[loss for loss, _ in runs])), jax.tree.map(mean, *[grads for _, grads in runs])


def worst_leaf(grads, want, error: str = "norm", need_gradient: bool = False):
    """(the leaf that differs most, by how much): its error's norm over the
    reference's, or ("max") its largest entry over the reference's largest."""
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    worst = ("", 0.0)
    size = np.linalg.norm if error == "norm" else lambda a: np.max(np.abs(a))
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
        if size(ref) == 0:  # a leaf the reference gives no gradient has no relative error: an agreeing case has none
            assert not need_gradient, f"{jax.tree_util.keystr(path)} has no gradient in the reference"
            continue
        rel = float(size(got - ref) / size(ref))
        if rel > worst[1]:
            worst = (jax.tree_util.keystr(path), rel)
    return worst


# -- the tests an entry's fields bring ---------------------------------------------------------


def test_loss_and_every_gradient_leaf_against_the_plain_reference(arch, case) -> None:
    """Float32 on both sides, so what differs is the order of sums: the loss
    and every leaf's gradient to the entry's tolerances — and a variant that
    leaves a piece of the published mathematics out, or puts it in the wrong
    layers, fails the comparison that the whole passes, by three times the
    tolerance and more."""
    loss, counters, grads = program_run(arch, case.config, case.variant, case.seed)
    want_loss, want = reference_run(arch, case.config, case.seed, _weights_key(arch, case.variant))
    loss_rel = abs(loss - want_loss) / abs(want_loss)
    if case.reading == "loss":
        assert not case.agrees and loss_rel > 10 * arch.loss_tolerance, (case.id, loss_rel)
        return
    if arch.prune is not None:
        want = arch.prune(want, case.variant)
    if case.stack is not None:
        assert set(grads) == set(want) == set(arch.stacks)
        grads, want = grads[case.stack], want[case.stack]
    leaf, rel = worst_leaf(grads, want, arch.leaf_error, need_gradient=case.agrees)
    if not case.agrees:
        assert rel > 3 * arch.leaf_tolerance, f"{case.id}: the comparison did not see it ({leaf} {rel}, loss {loss_rel})"
        return
    assert rel <= arch.leaf_tolerance and loss_rel <= arch.loss_tolerance, (leaf, rel, loss_rel)
    if arch.counters is not None:
        arch.counters(counters, arch.configs[case.config])


def test_rematerialised_layers_give_the_gradients_of_the_stored_ones(arch, walk) -> None:
    """`remat`, with and without the policy that keeps each layer's attention
    output and row statistics: what is recomputed is not computed differently
    — the loss (to `remat_ulps`: XLA:CPU contracts one product of RoPE into a
    sum, and which one differs under `jax.checkpoint`), every integer counter,
    every leaf to 1e-6 — and agrees with the reference as the stored walk does."""
    config, seed, _ = arch.remat
    loss, stored_counters, stored = program_run(arch, config, "as_published", seed)
    again_loss, counters, again = program_run(arch, config, walk, seed)
    assert abs(again_loss - loss) <= arch.remat_ulps * float(np.spacing(np.float32(loss)))
    for name, value in counters.items():
        if jnp.issubdtype(value.dtype, jnp.integer):
            assert np.array_equal(np.asarray(value), np.asarray(stored_counters[name])), name
    leaf, rel = worst_leaf(again, stored)
    assert rel < 1e-6, (leaf, rel)
    leaf, rel = worst_leaf(again, reference_run(arch, config, seed)[1], arch.leaf_error)
    assert rel <= arch.leaf_tolerance, (leaf, rel)


def test_a_model_without_a_piece_is_another_model(arch, piece) -> None:
    """Each piece of the mathematics moves some leaf's gradient by far more
    than the comparison's tolerance: the reference without it against the
    program as published, or the program without it (or with a wrong
    mechanism in its place) against the reference as published."""
    config, seed = arch.pieces_at
    if piece.side == "reference":
        _, _, got = program_run(arch, config, "as_published", seed)
        _, want = reference_run(arch, config, seed, None, piece.how)
    else:
        _, want = reference_run(arch, config, seed)
        weights, data = inputs(arch, config, seed)
        cfg, bias = program_cfg(arch, config)
        patch, cfg, weights = piece.how(cfg, weights)
        with arch.tracing(), patch:
            got = jax.jit(jax.grad(lambda p: loss_and_counters(p, data, cfg, router_bias=bias)[0]))(weights)
    leaf, rel = worst_leaf(got, want, arch.leaf_error)
    assert rel > arch.piece_floor, f"{piece.id}: the comparison did not see it ({leaf} {rel})"


def _value_and_input_gradients(layer: ExpertLayer, fn):
    """`fn(*inputs) -> (y, aux)` jitted once with the gradient of a seeded
    scalar of y with respect to every input: ((y, aux), gradients)."""
    def scalar(*args):
        y, aux = fn(*args)
        return jnp.sum(jnp.sin(layer.sin * y)), (y, aux)

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.jit(jax.value_and_grad(scalar, argnums=tuple(range(len(layer.inputs))), has_aux=True))(*layer.inputs)
    return out, grads


@functools.lru_cache(maxsize=None)
def _expert_layer(arch: Architecture):
    """The entry's layer and the uncut reference's values and gradients on it:
    the same for every count of chips."""
    layer = arch.expert_layer()
    return layer, _value_and_input_gradients(layer, layer.uncut)


def test_the_shares_add_up_to_the_uncut_layer(arch, chips) -> None:
    """What every chip of an expert-parallel layer computes of the routed
    experts, summed over the chips, with the shared expert (where there is one)
    counted ONCE, is what the uncut plain reference gives for the whole layer
    — values and the gradient of every input; the shares' held rows are all the
    assignments, and none is dropped.  All of a count's shares are one jitted
    function: `held_first` is a Python number in `moe_layer`, so a share is
    traced a rank, and compiled with the others."""
    layer, ((want, aux), dwant) = _expert_layer(arch)
    count = layer.experts // chips

    def summed(*args):
        shares = [layer.share(r * count, count, layer.shared and r == 0, *args) for r in range(chips)]
        alone = layer.share(0, count, False, *args)[0] if layer.shared else None
        return sum(y for y, _ in shares), ([stats for _, stats in shares], shares[0][0], alone)

    (got, (stats, first, alone)), dgot = _value_and_input_gradients(layer, summed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=layer.atol)
    for a, b in zip(dgot, dwant):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=layer.grad_rtol, atol=layer.grad_atol)
    skipped = int(stats[0].get("skipped", 0))
    assert sum(int(st["rows_held"]) for st in stats) + skipped == int(stats[0]["assignments"]) == layer.assignments
    assert all(int(st["dropped"]) == 0 for st in stats)
    assert all(np.array_equal(st["tokens_per_expert"], stats[0]["tokens_per_expert"]) for st in stats)
    if layer.shared:  # a share with the shared expert is that share plus the shared expert
        assert float(jnp.max(jnp.abs(first - alone))) > 0.1
    if layer.facts is not None:
        layer.facts(stats, aux, dwant)


def test_the_tree_is_the_reference_s(arch) -> None:
    """`init_params` and the reference's `make_weights` give one tree — the
    stacks, the leaves' names and shapes — and `param_axes` names every leaf."""
    config = arch.configs[arch.tree_config] if arch.tree_config else BENCH.config(arch.published)
    cfg = arch.program.transformer_config(config)
    ours = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    theirs = jax.eval_shape(lambda: arch.reference.make_weights(1, config))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [l.shape for l in jax.tree.leaves(ours)] == [l.shape for l in jax.tree.leaves(theirs)]
    axes = jax.tree.leaves(param_axes(cfg), is_leaf=lambda a: isinstance(a, tuple))
    assert jax.tree.structure(ours) == jax.tree.structure(param_axes(cfg), is_leaf=lambda a: isinstance(a, tuple))
    assert [len(a) for a in axes] == [l.ndim for l in jax.tree.leaves(ours)]
    arch.tree_facts(cfg, ours)


def test_the_published_configuration_is_handed_over_whole(arch) -> None:
    published = BENCH.config(arch.published)
    assert published["architecture"] == arch.name
    arch.published_facts(arch.program.transformer_config(published), published)


def test_the_adapter_raises_on_what_it_does_not_honour(arch, refusal) -> None:
    _, change, message, *also_the_reference = refusal
    config = dict(arch.configs[arch.refusal_config], **change)
    with pytest.raises(ValueError, match=message):
        arch.program.transformer_config(config)
    if also_the_reference:
        with pytest.raises(ValueError):
            arch.reference.sizes_of(config)


def tiny_of_the_small_model(arch_name: str, config, data, tree_facts, facts) -> Tiny:
    """The small configuration's own tree from `init_params`, rematerialised in
    the scan, the same batch twice: what the newer entries send through."""
    program = BENCH.program(arch_name)
    cfg = dataclasses.replace(program.transformer_config(config), remat=True, remat_keeps_attention=True, scan_unroll=1)
    bias = jnp.asarray(program.router_bias(config))

    def params():
        tree = init_params(jax.random.PRNGKey(5), cfg)
        tree_facts(tree)
        return tree

    return Tiny(params, lambda p, b: loss_and_counters(p, b, cfg, router_bias=bias), lambda i: data, 2, facts)


def records(path, event):
    with open(path, encoding="utf-8") as f:
        return [r for r in map(json.loads, f) if r.get("event") == event]


def ft_steps(loss, params, batches, store, tmp_path, monkeypatch, optimizer=None):  # noqa: F811
    """`ft_step`s of a loss with counters under a real Manager, one a batch:
    (the TrainStep, the parameters after, the metrics stream's path)."""
    path = tmp_path / "stream.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    client = MagicMock()
    client._quorum.return_value = make_quorum()
    client.should_commit.return_value = True
    manager, _, _ = make_manager(store, client_mock=client)
    ftmesh = ft_init_mesh({"data": 1}, devices=jax.devices()[:1])
    ftmesh.manager = manager
    step = TrainStep(ftmesh, optimizer or optax.adamw(1e-3), loss, loss_has_counters=True, overlap_commit=False)
    opt = step.init_opt_state(params)
    try:
        for data in batches:
            manager.start_quorum()
            params, opt, value, committed = step.ft_step(params, opt, data)
            assert committed and np.isfinite(float(value))
    finally:
        manager.shutdown()
    return step, params, path


def test_the_tree_goes_through(arch, through, store, tmp_path, monkeypatch) -> None:  # noqa: F811
    """The architecture's tree — its stacks of unequal leaf sets, its leaves of
    a few elements — through `ft_step` under a real Manager (the same names
    and shapes come back, the counters land in the next step's summary), a
    heal's transport and the bucket plan with the disk checkpoint (bit for bit)."""
    tiny = arch.tiny()
    params = tiny.params()
    leaves = jax.tree.leaves(params)
    if through == "ft_step":
        before = jax.tree.map(np.asarray, params)  # `ft_step` donates its arguments
        step, params, path = ft_steps(tiny.loss, params, [tiny.batch(i) for i in range(tiny.steps)], store, tmp_path, monkeypatch)
        assert jax.tree.structure(params) == jax.tree.structure(before)
        moved = {jax.tree_util.keystr(p) for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                                                              jax.tree.leaves(before)) if not np.array_equal(np.asarray(a), b)}
        summaries = records(path, "step_summary")
        assert len(summaries) == tiny.steps and all(s["counters_step"] == s["step"] - 1 for s in summaries[1:])
        tiny.facts(moved, summaries, step, params)
        return
    if through == "heal":
        from torchft_tpu.checkpointing.http_transport import HTTPTransport

        donor, healer = HTTPTransport(timeout=30.0), HTTPTransport(timeout=30.0)
        try:
            donor.send_checkpoint([1], 7, {"params": params}, 30.0)
            back = healer.recv_checkpoint(0, donor.metadata(), 7, 30.0)["params"]
        finally:
            donor.shutdown()
            healer.shutdown()
    else:
        from torchft_tpu.checkpointing.disk import DiskCheckpointer
        from torchft_tpu.ddp import plan_buckets

        buckets = plan_buckets([(l.shape, l.dtype) for l in leaves], 1 << 14)
        assert sorted(i for b in buckets for i in b.indices) == list(range(len(leaves))) and len(buckets) > 2
        ckpt = DiskCheckpointer(str(tmp_path))
        try:
            ckpt.save(4, {"params": params})
            ckpt.wait()
            back = ckpt.restore(4)["params"]
        finally:
            ckpt.shutdown()
    assert jax.tree.structure(back) == jax.tree.structure(params)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(jax.tree.leaves(back), leaves))
