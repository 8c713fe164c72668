#!/usr/bin/env python
"""Generates docs/reference.md: a per-module API reference of every public
symbol (signature + docstring summary), introspected from the live package.

The reference ships a Sphinx autodoc site over its 11 public modules
(/root/reference/docs/source/index.rst, conf.py); this is the TPU build's
generated equivalent — no doc toolchain in this image, so the generator is
~100 lines of inspect.  Re-run after API changes:

    python tools/gen_api_docs.py          # writes docs/reference.md
    python tools/gen_api_docs.py --check  # exit 1 if out of date (CI/test)
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Public module set (superset of the reference's docs/source/*.rst list:
# manager, optim, ddp, local_sgd, data, checkpointing, coordination,
# process_group->collectives, parameter_server — plus the TPU build's own
# additions).
MODULES = [
    "torchft_tpu",
    "torchft_tpu.manager",
    "torchft_tpu.collectives",
    "torchft_tpu.baby",
    "torchft_tpu.futures",
    "torchft_tpu.d2h_lease",
    "torchft_tpu.checkpointing.transport",
    "torchft_tpu.checkpointing.http_transport",
    "torchft_tpu.checkpointing.collective_transport",
    "torchft_tpu.checkpointing.disk",
    "torchft_tpu.checkpointing.serialization",
    "torchft_tpu.checkpointing.integrity",
    "torchft_tpu.ec.gf",
    "torchft_tpu.ec.encoder",
    "torchft_tpu.ec.placement",
    "torchft_tpu.ec.store",
    "torchft_tpu.ddp",
    "torchft_tpu.optim",
    "torchft_tpu.local_sgd",
    "torchft_tpu.semisync.diloco",
    "torchft_tpu.semisync.engine",
    "torchft_tpu.semisync.fragments",
    "torchft_tpu.semisync.codec",
    "torchft_tpu.semisync.metrics",
    "torchft_tpu.data",
    "torchft_tpu.parallel.mesh",
    "torchft_tpu.parallel.trainer",
    "torchft_tpu.parallel.sharding",
    "torchft_tpu.parallel.pipeline",
    "torchft_tpu.models.transformer",
    "torchft_tpu.models.mixer",
    "torchft_tpu.models.mixers",
    "torchft_tpu.models.attention",
    "torchft_tpu.models.kda",
    "torchft_tpu.models.gdn",
    "torchft_tpu.models.mamba",
    "torchft_tpu.models.rope",
    "torchft_tpu.models.moe",
    "torchft_tpu.models.convnet",
    "torchft_tpu.ops.attention",
    "torchft_tpu.ops.cross_entropy",
    "torchft_tpu.ops.grouped_matmul",
    "torchft_tpu.ops.rmsnorm",
    "torchft_tpu.ops.sparse_attention",
    "torchft_tpu.ops.delta_attention",
    "torchft_tpu.ops.ssd",
    "torchft_tpu.ops.ring_attention",
    "torchft_tpu.ops.ulysses",
    "torchft_tpu.coordination",
    "torchft_tpu.metrics",
    "torchft_tpu.obs.spans",
    "torchft_tpu.obs.opmap",
    "torchft_tpu.obs.report",
    "torchft_tpu.obs.trace",
    "torchft_tpu.obs.flight",
    "torchft_tpu.obs.prom",
    "torchft_tpu.multihost",
    "torchft_tpu.ha.lease",
    "torchft_tpu.ha.replica",
    "torchft_tpu.ha.backoff",
    "torchft_tpu.federation.region",
    "torchft_tpu.federation.root",
    "torchft_tpu.launch",
    "torchft_tpu.lighthouse_cli",
    "torchft_tpu.parameter_server",
]


def _public_names(mod) -> list[str]:
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return [
        n
        for n, obj in vars(mod).items()
        if not n.startswith("_")
        and (inspect.isclass(obj) or inspect.isfunction(obj))
        and getattr(obj, "__module__", None) == mod.__name__
    ]


def _sig(obj) -> str:
    import re

    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # Default values whose repr embeds a memory address (dataclass
    # factories, bound objects) or the state of a stream (sys.stdout, whose
    # repr nests "<stdout>" and names a mode that differs under pytest) are
    # unstable across runs: innermost brackets first, until none is left.
    while True:
        sig, n = re.subn(r"<[^<>]*>", "...", sig)
        if not n:
            return sig


def _summary(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    first = doc.strip().split("\n\n")[0].replace("\n", " ").strip()
    return first


def render() -> str:
    out = [
        "# API reference (generated)",
        "",
        "Every public symbol, per module — regenerate with "
        "`python tools/gen_api_docs.py` (checked by "
        "tests/test_packaging.py).  Narrative docs: docs/api.md, "
        "docs/architecture.md, docs/getting_started.md.",
        "",
    ]
    for modname in MODULES:
        mod = importlib.import_module(modname)
        out.append(f"## {modname}")
        out.append("")
        msum = _summary(mod)
        if msum:
            out.append(msum)
            out.append("")
        for name in _public_names(mod):
            obj = getattr(mod, name, None)
            if obj is None:
                continue
            if inspect.isclass(obj):
                out.append(f"### `{name}{_sig(obj)}`")
                s = _summary(obj)
                if s:
                    out.append("")
                    out.append(s)
                out.append("")
                for mname, meth in sorted(vars(obj).items()):
                    if mname.startswith("_") or not callable(meth):
                        continue
                    ms = _summary(meth)
                    out.append(
                        f"- `{mname}{_sig(meth)}`" + (f" — {ms}" if ms else "")
                    )
                out.append("")
            elif inspect.isfunction(obj):
                s = _summary(obj)
                out.append(f"### `{name}{_sig(obj)}`")
                if s:
                    out.append("")
                    out.append(s)
                out.append("")
            else:
                out.append(f"### `{name}`")
                s = _summary(obj) if not isinstance(obj, (int, str)) else ""
                if s:
                    out.append("")
                    out.append(s)
                out.append("")
    return "\n".join(out) + "\n"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()
    path = os.path.join(os.path.dirname(__file__), "..", "docs", "reference.md")
    text = render()
    if args.check:
        with open(path) as f:
            if f.read() != text:
                print("docs/reference.md is out of date; run tools/gen_api_docs.py")
                raise SystemExit(1)
        print("docs/reference.md up to date")
        return
    with open(path, "w") as f:
        f.write(text)
    print(f"wrote {os.path.normpath(path)} ({len(text.splitlines())} lines)")


if __name__ == "__main__":
    main()
