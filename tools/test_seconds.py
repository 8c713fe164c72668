#!/usr/bin/env python3
"""Case-seconds of a tier-1 run, by file and by test function.

Reads the junit file the driver's command writes (``--junitxml``) and prints
where the seconds are: every file with its case-seconds, its cases and its
share of the total, the test functions that hold the most, the longest file's
share and the run's wall time.  Under ``--dist loadfile`` a file is one
worker's, so the longest file bounds the run from below.

    python tools/test_seconds.py /tmp/_t1.xml [--top 25] [--workers 6] [--record tests/data/file_seconds.json]

`--workers` adds what the same files would take handed out longest first to
that many workers; `--record` writes the seconds a file, which `tests/conftest.py`
reads to hand them out in that order.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import xml.etree.ElementTree as ET


def read(path: str) -> dict:
    """``{"wall_s", "cases", "by_file": {file: [seconds, cases]},
    "by_test": {(file, function): [seconds, cases]}}`` of one junit file."""
    suites = ET.parse(path).getroot().iter("testsuite")
    wall_s, by_file, by_test = 0.0, collections.Counter(), collections.Counter()
    n_file, n_test = collections.Counter(), collections.Counter()
    for suite in suites:
        wall_s += float(suite.get("time", 0.0))
        for case in suite.iter("testcase"):
            file = case.get("classname", "").replace(".", "/") + ".py"
            test = (file, case.get("name", "").split("[", 1)[0])
            seconds = float(case.get("time", 0.0))
            by_file[file] += seconds
            by_test[test] += seconds
            n_file[file] += 1
            n_test[test] += 1
    return {
        "wall_s": wall_s,
        "cases": sum(n_file.values()),
        "by_file": {f: [s, n_file[f]] for f, s in by_file.most_common()},
        "by_test": {t: [s, n_test[t]] for t, s in by_test.most_common()},
    }


def report(run: dict, top: int = 25) -> str:
    total = sum(s for s, _ in run["by_file"].values()) or 1.0
    lines = [
        f"{run['cases']} cases, {total:.0f} case-seconds, {run['wall_s']:.0f} s of wall time",
        "",
        f"{'file':<44}{'case-s':>8}{'cases':>7}{'share':>8}",
    ]
    for file, (seconds, cases) in run["by_file"].items():
        lines.append(f"{file:<44}{seconds:>8.0f}{cases:>7}{seconds / total:>8.1%}")
    lines += ["", f"{'test function':<88}{'case-s':>8}{'cases':>7}"]
    for (file, test), (seconds, cases) in list(run["by_test"].items())[:top]:
        lines.append(f"{file + '::' + test:<88}{seconds:>8.0f}{cases:>7}")
    longest, (seconds, _) = next(iter(run["by_file"].items()), ("", (0.0, 0)))
    lines += ["", f"longest file: {longest} {seconds:.0f} s, {seconds / total:.1%} of the case-seconds"]
    return "\n".join(lines)


def packed(run: dict, workers: int) -> float:
    """The seconds of the run's files handed out longest first, each to the
    worker that is free first: no shorter than the longest file or the sum a worker."""
    free = [0.0] * workers
    for seconds, _ in sorted(run["by_file"].values(), reverse=True):
        free[free.index(min(free))] += seconds
    return max(free)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("junit")
    ap.add_argument("--top", type=int, default=25, help="test functions to list")
    ap.add_argument("--workers", type=int, help="also: the files' seconds handed out longest first to this many workers")
    ap.add_argument("--record", help="write the seconds a file to this JSON file (tests/data/file_seconds.json)")
    args = ap.parse_args(argv)
    run = read(args.junit)
    print(report(run, args.top))
    if args.workers:
        print(f"longest first on {args.workers} workers: {packed(run, args.workers):.0f} s")
    if args.record:
        try:  # the files to keep apart are the table's own: written by hand, kept
            with open(args.record, encoding="utf-8") as f:
                apart = json.load(f).get("apart", [])
        except (OSError, ValueError):
            apart = []
        with open(args.record, "w", encoding="utf-8") as f:
            json.dump({"from": "tools/test_seconds.py --record", "wall_s": round(run["wall_s"]), "apart": apart,
                       "seconds": {file: round(s, 1) for file, (s, _) in run["by_file"].items()}}, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
