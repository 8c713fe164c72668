"""The share of the causal triangle's (query, key) pairs that the window layers'
kernels compute, read from the program that ran: over the `tpuft_swa_*` custom
calls of the compiled gradient program (`TrainStep.compiled_texts`, asked of
the live train step after the window), the grid steps of each call
(`iteration_bounds` in the kernel's body, a tile of block_q x block_k pairs a
step) over the steps a triangular walk of the same call has (n (n + 1) / 2 a
head at n = sequence / block_q tiles a side).  At 16,384 positions, a window
of 512 and 512 x 512 tiles a band walk reads (2n - 1) / (n (n + 1) / 2) = 63 /
528 = 0.1193 — about twice the 0.0615 of the triangle's pairs that a query
attends to, since the mask drops half of each band tile — and a window layer
that walked the whole triangle would read 1.  The calls found, with their
grids, go to `g0.swa_grids.json` in the run's directory.  None where the
program hands out no compiled text or has no such kernel (off the chip it
runs none)."""

import base64
import json
import os
import re

LAYER = "kernels"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"

FILE = "g0.swa_grids.json"
_NAME = re.compile(r"tpuft_swa_\w+")
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_OPERAND = re.compile(r"\w+\[(\d+),(\d+),(\d+)\]")


def grids(text):
    """[{name, grid, block_q, seq}] of the `tpuft_swa_*` kernel calls in a
    compiled program's text."""
    from jax._src.lib.mlir import ir

    found = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        call, body = _NAME.search(line[:line.index("backend_config=")]), _BODY.search(line)
        if not call or not body:
            continue
        context = ir.Context()
        context.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(body.group(1)), context)
        kernel = next(op for op in module.body.operations if "iteration_bounds" in op.attributes)
        # the kernel's arguments: the grid's indices, the walk's tables, then q's block [1, block_q, d]
        blocks = [a.type.shape for a in kernel.regions[0].blocks[0].arguments if isinstance(a.type, ir.ShapedType)]
        block_q = next(shape for shape in blocks if len(shape) == 3)[1]
        layouts = line[line.index("operand_layout_constraints="):]
        seq = int(_OPERAND.search(layouts).group(2))  # q: [batch * heads, sequence, d]
        found.append({"name": call.group(0), "grid": list(kernel.attributes["iteration_bounds"]),
                      "block_q": block_q, "seq": seq})
    return found


def read(ctx):
    try:
        from torchft_tpu.obs import opmap

        texts = opmap.train_steps()[-1].compiled_texts()
    except (ImportError, IndexError, AttributeError):  # a program without the method, or no live train step
        return None
    found = [g for text in texts.values() for g in grids(text)]
    if not found:
        return None
    steps = triangle = 0
    for g in found:
        n = g["seq"] // g["block_q"]
        g["triangle"] = [g["grid"][0], n * (n + 1) // 2]
        steps += g["grid"][0] * g["grid"][1]
        triangle += g["triangle"][0] * g["triangle"][1]
    run_dir = os.path.dirname(os.environ.get("TPUFT_METRICS_PATH", ""))
    if os.path.isdir(run_dir):
        with open(os.path.join(run_dir, FILE), "w", encoding="utf-8") as f:
            json.dump({"cell": ctx["cell"]["name"], "calls": found, "steps": steps, "triangle": triangle}, f)
    return steps / triangle
