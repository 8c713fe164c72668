"""`tools/test_seconds.py` on a recorded fragment of the driver's junit file."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import test_seconds  # noqa: E402

FRAGMENT = os.path.join(ROOT, "tests", "data", "junit_fragment.xml")


def test_case_seconds_by_file_and_by_function() -> None:
    run = test_seconds.read(FRAGMENT)
    assert run["wall_s"] == 61.5 and run["cases"] == 7
    assert list(run["by_file"]) == ["tests/test_ops.py", "tests/test_mamba2_moe.py", "tests/test_settings.py"]  # longest first
    assert run["by_file"]["tests/test_ops.py"] == [42.0, 3] and run["by_file"]["tests/test_mamba2_moe.py"] == [30.5, 2]
    # a test function's cases are summed whatever their parameters; failed and skipped cases count their seconds
    leaves = ("tests/test_mamba2_moe.py", "test_loss_and_every_gradient_leaf_against_the_plain_reference")
    assert run["by_test"][leaves] == [30.5, 2]
    assert run["by_test"][("tests/test_ops.py", "test_flash_attention_reference_path")] == [1.75, 2]
    assert next(iter(run["by_test"])) == ("tests/test_ops.py", "test_ring_attention_grads_match_full")


def test_the_report_names_the_longest_file_and_the_wall_time() -> None:
    out = subprocess.run([sys.executable, os.path.join(ROOT, "tools", "test_seconds.py"), FRAGMENT, "--top", "2"],
                         capture_output=True, text=True, check=True).stdout
    lines = out.splitlines()
    assert lines[0] == "7 cases, 73 case-seconds, 62 s of wall time"
    assert lines[-1] == "longest file: tests/test_ops.py 42 s, 57.9% of the case-seconds"
    assert sum("::test_" in line for line in lines) == 2  # --top
    assert any(line.split() == ["tests/test_mamba2_moe.py", "30", "2", "42.1%"] for line in lines)


def test_the_seconds_a_file_are_recorded_for_the_order_of_the_run(tmp_path) -> None:
    import json

    out = tmp_path / "file_seconds.json"
    assert test_seconds.main([FRAGMENT, "--workers", "2", "--record", str(out)]) == 0
    recorded = json.loads(out.read_text())
    assert recorded["seconds"] == {"tests/test_ops.py": 42.0, "tests/test_mamba2_moe.py": 30.5, "tests/test_settings.py": 0.0}
    run = test_seconds.read(FRAGMENT)
    assert test_seconds.packed(run, 2) == 42.0 and test_seconds.packed(run, 1) == 72.504  # the longest file bounds it
