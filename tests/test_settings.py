"""The rule for settings: a ``TPUFT_*`` name the program reads is a deployment
setting, a hand-over from a parent process to its child, or an option that
something in the repo sets.  A name nothing sets has one value in use, and that
is a constant, not a setting (the ``simplicity-review`` guide, Options).  No JAX."""

import functools
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
NAME = re.compile(r"\bTPUFT_[A-Z0-9_]+\b")

# What a deployment has and a repo cannot know: they stay configurable whether
# or not anything here sets them.
DEPLOYMENT = {
    # addresses
    "TPUFT_LIGHTHOUSE", "TPUFT_STORE",
    # ports and binds
    "TPUFT_MANAGER_PORT", "TPUFT_COORD_PORT", "TPUFT_WORKER_METRICS_PORT", "TPUFT_WORKER_METRICS_BIND",
    # paths
    "TPUFT_DRAIN_DIR", "TPUFT_FLIGHT_DIR", "TPUFT_HOP_DUMP_DIR", "TPUFT_METRICS_PATH", "TPUFT_SPARE_FILE",
    # tokens, the log level, the kind of link between the groups
    "TPUFT_ADMIN_TOKEN", "TPUFT_LOG", "TPUFT_LINK_PROFILE",
    # the platform's preemption notice: whether to poll for it, and where
    "TPUFT_GCE_DRAIN_POLL", "TPUFT_GCE_METADATA_URL",
}
# Written by a parent process for the child it starts (launch.py for a
# supervised group, ha/replica.py around one native call): no one else's to set.
HAND_OVERS = {"TPUFT_DRAIN_SUPERVISED", "TPUFT_HA_START_FOLLOWER"}
# Read in native/src/lighthouse.cc and set by nothing: left for a PR that
# rebuilds the library anyway (ROADMAP C3).  Nothing is to be added here.
NATIVE_LEFTOVER = {"TPUFT_GOODPUT_DIP_RATIO"}


def names_in(text: str) -> set:
    """The settings a text names.  ``TPUFT_X_ENV`` is a Python constant whose
    value, the name itself, stands beside it; ``TPUFT_ELASTIC_*`` is a pattern."""
    return {n for n in NAME.findall(text) if not n.endswith(("_ENV", "_"))}


def read_by_the_program() -> set:
    sources = [*(ROOT / "torchft_tpu").rglob("*.py"), *(ROOT / "native" / "src").glob("*.cc"),
               *(ROOT / "native" / "src").glob("*.h")]
    return set().union(*(names_in(p.read_text()) for p in sources))


@functools.lru_cache(maxsize=None)
def set_by_the_repo() -> frozenset:
    """Every name a test, an example, a benchmark file, a tool or the chip
    smoke mentions: the places that run the program at another value."""
    files = [ROOT / "chip_smoke.py"]
    for d in ("tests", "examples", "benchmark", "tools"):
        files += [p for p in (ROOT / d).rglob("*") if p.suffix in (".py", ".json", ".sh") and p != pathlib.Path(__file__)]
    return frozenset().union(*(names_in(p.read_text(errors="replace")) for p in files))


READ = read_by_the_program()
OPTIONS = sorted(READ - DEPLOYMENT - HAND_OVERS - NATIVE_LEFTOVER)


def test_the_lists_name_only_what_the_program_reads() -> None:
    assert DEPLOYMENT | HAND_OVERS | NATIVE_LEFTOVER <= READ
    assert len(OPTIONS) > 30  # the scan found the sources


@pytest.mark.parametrize("name", OPTIONS)
def test_an_option_is_set_by_something_in_the_repo(name) -> None:
    assert name in set_by_the_repo(), (
        f"{name} is read by the program and set by no test, example, benchmark file, tool or chip_smoke.py: "
        "make it a constant (or an argument a caller passes), not a setting"
    )


def test_every_setting_the_program_reads_is_in_the_api_guide_and_no_other() -> None:
    guide = names_in((ROOT / "docs" / "api.md").read_text())
    assert sorted(READ - guide) == [], "read by the program, missing from docs/api.md"
    assert sorted(guide - READ) == [], "in docs/api.md, read by nothing"
