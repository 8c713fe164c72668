"""Test configuration: put JAX on a virtual 8-device CPU platform so
multi-chip sharding paths run without TPU hardware.  The chip path is
`chip_smoke.py`, run through the chip tool; `tests/test_chip_compile*.py`
compile for a described chip without one.

And hands the files to xdist's workers longest first.  Under `--dist loadfile`
a file is one worker's, and xdist's own order is by a file's NUMBER of tests,
most first: a file of three whole-program compiles would start last and be the
run's tail.  `tests/data/file_seconds.json` (written by `tools/test_seconds.py
--record` from a run's junit file) holds the last recorded seconds a file; a
file it does not know goes first.  The files it lists as `apart` — the
described-chip compiles, each of which holds four cores: two at once take twice
as long each — are spread through the run and not started together."""

import json
import os
import sys

# JAX reads both at import; every test process and every child it starts
# stays on the CPU.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_FILE_SECONDS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "file_seconds.json")


def pytest_configure(config) -> None:
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False  # keep the order of collection, which the hook below makes


def _order(files, seconds, apart) -> list:
    """`files` longest first, but for the files of `apart`: the first of them
    starts the run and each next one follows an equal share of the others' seconds."""
    rest = sorted((f for f in files if f not in apart), key=lambda f: -seconds.get(f, float("inf")))
    spread = [f for f in apart if f in files]
    share = sum(seconds.get(f, 0.0) for f in rest) / max(len(spread), 1)
    ordered, handed_out, started = [], 0.0, 0
    for f in rest:
        if started < len(spread) and handed_out >= share * started:
            ordered.append(spread[started])
            started += 1
        ordered.append(f)
        handed_out += seconds.get(f, 0.0)
    return ordered + spread[started:]


def pytest_collection_modifyitems(items) -> None:
    try:
        with open(_FILE_SECONDS, encoding="utf-8") as f:
            table = json.load(f)
    except (OSError, ValueError):
        return
    files = list(dict.fromkeys(item.nodeid.split("::")[0] for item in items))
    place = {f: i for i, f in enumerate(_order(files, table.get("seconds", {}), table.get("apart", [])))}
    items.sort(key=lambda item: place[item.nodeid.split("::")[0]])  # stable: a file's own order stays
