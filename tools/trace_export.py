#!/usr/bin/env python3
"""Export merged multi-replica metrics JSONL as a Chrome/Perfetto trace.

Any run's metrics stream (``TPUFT_METRICS_PATH``) becomes a viewable
timeline::

    python tools/trace_export.py <workdir>/metrics.jsonl
    # -> <workdir>/trace.json; open in ui.perfetto.dev

or point it at a directory and it collects every ``*.jsonl`` inside::

    python tools/trace_export.py --workdir <workdir>

The output is standard Chrome trace-event JSON: one process per replica
group, one track per incarnation (background snapshot work on a sub-track),
phase slices carrying ``step``/``slice_gen`` args, and fault / drain /
alert instant events — clock-aligned across replicas via the
``step_summary`` commit barrier (torchft_tpu/obs/trace.py).

``--quick`` runs the tier-1 smoke: build a synthetic 2-replica stream,
export it, validate the trace schema, print a JSON summary, exit non-zero
on any problem.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/trace_export.py",
        description="Merge tpu-ft metrics JSONL streams into a Chrome/"
        "Perfetto trace.json (one track per replica).",
    )
    ap.add_argument("paths", nargs="*", help="metrics.jsonl file(s)")
    ap.add_argument(
        "--workdir", help="collect every *.jsonl (and flight_*.json flight-"
        "recorder dump) under this directory instead"
    )
    ap.add_argument(
        "--flight",
        action="append",
        default=[],
        metavar="FLIGHT_JSON",
        help="flight-recorder dump(s) to merge as a control-plane track",
    )
    ap.add_argument(
        "--hops",
        action="append",
        default=[],
        metavar="HOPS_JSON",
        help="data-plane hop-timeline dump(s) (hops_<replica>.json, from "
        "TPUFT_HOP_DUMP_DIR or a bench) to merge as per-lane tracks",
    )
    ap.add_argument("-o", "--out", help="output path (default: trace.json next "
                    "to the first input)")
    ap.add_argument(
        "--no-align", action="store_true",
        help="skip the step_summary commit-barrier clock alignment",
    )
    ap.add_argument(
        "--quick", action="store_true",
        help="self-contained smoke: synthetic 2-replica stream -> export -> "
        "schema validation (used by tier-1 tests)",
    )
    args = ap.parse_args(argv)

    from torchft_tpu.obs import trace as obs_trace

    if args.quick:
        # Worker stream + the lighthouse's synthetic flight view of the
        # same run + the ring engines' synthetic hop timeline: the smoke
        # covers the control-plane AND data-plane tracks end to end.
        events = obs_trace.synthetic_stream(n_replicas=2, steps=4)
        events += obs_trace.synthetic_flight_stream(n_replicas=2, steps=4)
        events += obs_trace.synthetic_hop_stream(n_replicas=2, steps=4)
        events.sort(key=lambda ev: ev["ts"])
        built = obs_trace.build_trace(events, align=not args.no_align)
        problems = obs_trace.validate_trace(built)
        cp_tracks = built.get("otherData", {}).get("control_plane", {})
        if not cp_tracks:
            problems.append("control-plane track missing from --quick trace")
        dp_tracks = sum(
            1
            for ev in built["traceEvents"]
            if ev.get("ph") == "M"
            and ev.get("name") == "thread_name"
            and " dp:" in str(ev.get("args", {}).get("name", ""))
        )
        if not dp_tracks:
            problems.append("data-plane hop track missing from --quick trace")
        hop_slices = sum(
            1 for ev in built["traceEvents"] if ev.get("cat") == "hop"
        )
        if not hop_slices:
            problems.append("no hop slices in --quick trace")
        # Incident-bundle roundtrip (obs/incident.py): a synthetic kill
        # bundle built from the same stream must write, reload, and
        # verdict onto the injected victim group — the tier-1 pin that
        # the bundle schema and the verdict engine stay in sync.
        from torchft_tpu.obs import incident as obs_incident

        import shutil

        incident_ok = False
        broot = None
        try:
            broot = tempfile.mkdtemp(prefix="tpuft_incident_quick_")
            bundle = os.path.join(broot, "incident_4")
            os.makedirs(bundle, exist_ok=True)
            with open(
                os.path.join(bundle, "spans_tail.jsonl"), "w", encoding="utf-8"
            ) as f:
                for ev in events:
                    f.write(json.dumps(ev) + "\n")
            trig = {
                "id": 1, "reason": "replica_stale", "replica_id": "1:b1",
                "step": 4, "ts_ms": 1_700_000_002_400, "detail": 500.0,
            }
            with open(
                os.path.join(bundle, "incident.json"), "w", encoding="utf-8"
            ) as f:
                json.dump(
                    {"schema": 1, "incidents": [trig],
                     "artifacts": {"spans_tail.jsonl": "tail"}}, f
                )
            manifest = obs_incident.finalize_bundle(bundle, broot)
            v = manifest.get("verdict", {})
            incident_ok = (
                v.get("kind") == "kill"
                and v.get("replica") == "1"
                and v.get("lost_s") is not None
                and obs_incident.load_bundle(bundle)["manifest"]["incidents"]
            )
        except Exception as e:  # noqa: BLE001 — report, don't crash --quick
            problems.append(f"incident bundle roundtrip raised: {e}")
        finally:
            if broot is not None:
                shutil.rmtree(broot, ignore_errors=True)
        if not incident_ok and not problems:
            problems.append("incident bundle verdict failed to name the victim")
        out = args.out
        if out is None:
            fd, out = tempfile.mkstemp(prefix="tpuft_trace_", suffix=".json")
            os.close(fd)
        with open(out, "w", encoding="utf-8") as f:
            json.dump(built, f)
        print(
            json.dumps(
                {
                    "ok": not problems,
                    "out": out,
                    "input_events": len(events),
                    "trace_events": len(built["traceEvents"]),
                    "replicas": len(built.get("otherData", {}).get("replicas", {})),
                    "control_plane_tracks": len(cp_tracks),
                    "data_plane_tracks": dp_tracks,
                    "hop_slices": hop_slices,
                    "incident_bundle_ok": bool(incident_ok),
                    "problems": problems,
                }
            )
        )
        return 0 if not problems else 1

    paths = list(args.paths)
    flight_paths = list(args.flight)
    hops_paths = list(args.hops)
    if args.workdir:
        paths += sorted(
            glob.glob(os.path.join(args.workdir, "**", "*.jsonl"), recursive=True)
        )
        flight_paths += sorted(
            glob.glob(
                os.path.join(args.workdir, "**", "flight_*.json"), recursive=True
            )
        )
        hops_paths += sorted(
            glob.glob(
                os.path.join(args.workdir, "**", "hops_*.json"), recursive=True
            )
        )
    if not paths and not flight_paths and not hops_paths:
        ap.error(
            "no input: pass metrics.jsonl path(s), --flight, --hops, or "
            "--workdir"
        )
    first = (paths + flight_paths + hops_paths)[0]
    out = args.out or os.path.join(os.path.dirname(first) or ".", "trace.json")
    summary = obs_trace.export(
        paths, out, align=not args.no_align, flight_paths=flight_paths,
        hops_paths=hops_paths,
    )
    print(json.dumps(summary))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
