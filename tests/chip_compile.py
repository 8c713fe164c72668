"""What the described-chip compiles share (`tests/test_chip_compile*.py`, a file
a family of configurations so that six workers can share them): the described
`v5e:2x2` topology, one of its chips, and the readers of a compiled program's
text.

The topology is described inside a module-scoped fixture, imported by each of
those files, and nowhere else: the call must not run while any module is
imported (every xdist worker imports every test file), and a process that
loads the TPU's library beside another that has it needs
`ALLOW_MULTIPLE_LIBTPU_LOAD` (the driver's command sets it; run one file at a
time without it).  Everything compiles in the test's own process, with the
persistent compile cache off around it (a described-device entry cannot be read
back).  A compile that passes is not a chip run and is never reported as one.
"""

import os

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def models():
    import chip_smoke

    return {"flagship": chip_smoke.flagship_config(), "1b": chip_smoke.large_config()}


def compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def has_kernel(text: str, name: str) -> bool:
    import chip_smoke

    return chip_smoke.has_kernel(text, name)


def attention_calls(text: str) -> list:
    """The names of the compiled program's attention kernels, one entry per
    `tpu_custom_call` (a pallas kernel's `name=` is in its metadata)."""
    import re

    return [m.group(0) for line in text.splitlines() if "tpu_custom_call" in line and "custom-call(" in line
            for m in [re.search(r"tpuft_fa_[a-z_]*[a-z]", line)] if m]


def instructions(text: str) -> list:
    """(opcode, elements of the result) of every instruction with one array
    for a result in a compiled program's entry computation: what runs as an
    instruction of its own (a `reshape` inside a fusion's body costs what the
    fusion costs)."""
    import re

    found = []
    text = text[text.index("ENTRY "):]
    for m in re.finditer(r"^\s*(?:ROOT )?%?[\w.-]+ = \w+\[([\d,]*)\](?:\{[^}]*\})? ([\w-]+)\(", text, re.M):
        found.append((m.group(2), elements(m.group(1))))
    return found


def elements(dims: str) -> int:
    """Elements of an array whose shape the compiled text writes as `16384,8`."""
    import math

    return math.prod(int(d) for d in dims.split(",") if d)


def kernel_calls(text: str, prefix: str) -> list:
    """As `attention_calls`, for the kernels whose names start with `prefix`."""
    import re

    return [m.group(0) for line in text.splitlines() if "tpu_custom_call" in line and "custom-call(" in line
            for m in [re.search(prefix + r"[a-z_]*[a-z]", line)] if m]


def kernel_grids(text: str, prefix: str) -> list:
    """[(name, grid)] of the compiled kernel calls whose names start with
    `prefix`: the grid is `iteration_bounds` of the kernel's serialised body."""
    import base64
    import re

    from jax._src.lib.mlir import ir

    found = []
    for line in text.splitlines():
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        name = re.search(prefix + r"[a-z_]*[a-z]", line[:line.find("backend_config=")])
        if "tpu_custom_call" not in line or not body or not name:
            continue
        context = ir.Context()
        context.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(body.group(1)), context)
        kernel = next(op for op in module.body.operations if "iteration_bounds" in op.attributes)
        found.append((name.group(0), tuple(kernel.attributes["iteration_bounds"])))
    return found


def heads_a_step(text: str, prefix: str, bh: int) -> dict:
    """{kernel name: heads a grid step} over the compiled calls whose names
    start with `prefix`, each at batch * heads = ``bh``: the grid's outer axis
    is bh / H (since PR 52)."""
    found = {}
    for name, grid in kernel_grids(text, prefix):
        assert bh % grid[0] == 0, (name, grid)
        found.setdefault(name, set()).add(bh // grid[0])
    return {name: sorted(heads) for name, heads in found.items()}


def relayouts(text: str, scopes=("attn_proj", "attn", "attn_window"), at_least: int = 1 << 20) -> list:
    """[(name, dtype, dims, op_name)] of the instructions of a compiled
    program's entry computation that only move an array — a `copy`, a
    `transpose`, or a fusion whose root is one — inside the model's ``scopes``
    (a component of the instruction's `op_name`), results of ``at_least``
    elements or more: what stands between a projection and an attention
    kernel when the two disagree about where a head lies."""
    import re

    roots = {}
    for m in re.finditer(r"^%([\w.-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S):
        root = re.search(r"ROOT %?[\w.-]+ = [^ ]+ ([\w-]+)\(", m.group(2))
        roots[m.group(1)] = root.group(1) if root else ""
    found = []
    for line in text[text.index("ENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.-]+) = (\w+)\[([\d,]*)\][^ ]* ([\w-]+)\(", line)
        if not m or elements(m.group(3)) < at_least:
            continue
        name, dtype, dims, opcode = m.groups()
        called = re.search(r"calls=%([\w.-]+)", line)
        if opcode == "fusion" and called:
            opcode = roots.get(called.group(1), "")
        op_name = re.search(r'op_name="([^"]*)"', line)
        op_name = op_name.group(1) if op_name else ""
        if opcode in ("copy", "transpose") and set(op_name.split("/")) & set(scopes):
            found.append((name, dtype, tuple(int(d) for d in dims.split(",") if d), op_name))
    return found
