"""Federation contract tests (docs/wire.md "Federation").

Two layers, matching how the rest of the suite guards cross-layer
contracts:

- **Static pins** — wire methods 8-9, the `RegionDigest` field set
  (including the `root_gen` phantom-join fence), and the
  `tpuft_federation_*` / `tpuft_region_*` gauge names are each spelled in
  three places (native/src, proto, docs/wire.md) with nothing but these
  greps tying them together; a rename in one place would silently strand
  the others, exactly the drift the ledger-taxonomy pins exist for.
- **Live smoke** — `fleet_cells.run_federated_quick()`: 2 regions x 2
  groups through REAL child-lighthouse subprocesses with one worker
  SIGKILLed mid-window, gated on digest consistency across the kill, a
  reformed global quorum, and zero failed survivor commits.
"""

import os
import re

import fleet_cells

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(relpath: str) -> str:
    with open(os.path.join(REPO, relpath), "r", encoding="utf-8") as f:
        return f.read()


# ---------------------------------------------------------------------------
# Static pins: one federation wire surface, everywhere
# ---------------------------------------------------------------------------


def test_wire_method_numbers_pinned() -> None:
    wire_h = _read(os.path.join("native", "src", "wire.h"))
    assert re.search(r"kLighthouseRegionDigest\s*=\s*8\b", wire_h), (
        "RegionDigest must stay wire method 8 (frozen contract)"
    )
    assert re.search(r"kLighthouseRegions\s*=\s*9\b", wire_h), (
        "Regions must stay wire method 9 (frozen contract)"
    )
    wire_md = _read(os.path.join("docs", "wire.md"))
    assert "| 8 | Lighthouse.RegionDigest |" in wire_md, (
        "method 8 missing from the docs/wire.md method table"
    )
    assert "| 9 | Lighthouse.Regions |" in wire_md, (
        "method 9 missing from the docs/wire.md method table"
    )


def test_region_digest_proto_fields_pinned() -> None:
    proto = _read(os.path.join("proto", "tpuft.proto"))
    digest = re.search(r"message RegionDigest \{(.*?)\n\}", proto, re.S)
    assert digest, "RegionDigest message missing from proto"
    body = digest.group(1)
    for field, number in (
        ("region", 1),
        ("child_epoch", 2),
        ("seq", 3),
        ("members", 4),
        ("ledger_compute_seconds", 5),
        ("ledger_lost_seconds", 6),
        ("alerts_active", 7),
        ("incident_seq", 8),
        ("replicas_total", 9),
        ("replicas_fresh", 10),
        ("goodput_ratio", 11),
        ("root_gen", 12),
    ):
        assert re.search(rf"\b{field}\s*=\s*{number}\s*;", body), (
            f"RegionDigest.{field} must stay field {number}"
        )
    # The fence fields the docs explain must actually be documented.
    wire_md = _read(os.path.join("docs", "wire.md"))
    for name in ("root_gen", "child_epoch", "RegionMember", "RegionDigest",
                 "LighthouseRegionDigestResponse", "RegionInfo"):
        assert name in wire_md, f"{name} undocumented in docs/wire.md"
    # Downward control propagation rides the response.
    resp = re.search(
        r"message LighthouseRegionDigestResponse \{(.*?)\n\}", proto, re.S
    )
    assert resp, "LighthouseRegionDigestResponse missing from proto"
    for field in ("applied", "leader_epoch", "quorum", "quorum_gen",
                  "evict_prefixes", "drain_prefixes"):
        assert field in resp.group(1), (
            f"digest response field {field} missing from proto"
        )


def test_federation_gauges_and_endpoints_pinned() -> None:
    src = _read(os.path.join("native", "src", "lighthouse.cc"))
    wire_md = _read(os.path.join("docs", "wire.md"))
    for name in (
        "tpuft_federation_role",
        "tpuft_federation_digests_total",
        "tpuft_federation_digests_rejected_total",
        "tpuft_regions",
        "tpuft_region_replicas",
        "tpuft_region_replicas_fresh",
        "tpuft_region_digest_age_seconds",
        "tpuft_region_epoch",
        "tpuft_region_stale",
        "tpuft_region_goodput_ratio",
        "tpuft_region_alerts_active",
        "tpuft_region_compute_seconds_total",
        "tpuft_region_lost_seconds_total",
        "/regions.json",
        "region_stale",
    ):
        assert name in src, f"{name} missing from lighthouse.cc"
        assert name in wire_md, f"{name} undocumented in docs/wire.md"


# ---------------------------------------------------------------------------
# Live smoke: 2 regions x 2 groups, one SIGKILL, real child subprocesses
# ---------------------------------------------------------------------------


def test_federation_quick_smoke() -> None:
    out = fleet_cells.run_federated_quick()
    cell = out["cells"][0]
    assert cell["digest_consistency_pre"]["ok"] is True, cell
    assert cell["digest_consistency_post"]["ok"] is True, cell
    assert cell["quorum_reformed"] is True, cell
    assert cell["survivor_failed_commits"] == 0, cell
    # The federated fan-in claim at smoke scale: the root formed the
    # global quorum without fielding a single heartbeat RPC.
    assert cell["root_heartbeat_rpcs"] == 0, cell
    assert out["ok"] is True, cell
