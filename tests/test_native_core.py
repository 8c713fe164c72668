"""Runs the native C++ unit suite (native/tests/test_core.cc) as part of
the default pytest run, so `python -m pytest tests/` covers BOTH halves of
the stack — the reference's `scripts/test.sh` runs `cargo test` next to
pytest the same way (SURVEY.md §4).

With the full toolchain the binary is (re)built by the same cmake/ninja
auto-build the bindings use.  Toolchain-less containers (no cmake/ninja/
protoc — the environment native/gen_pb_local.py exists for) fall back to
the same plain-g++ recipe that builds the shared library, mtime-cached
under native/build-g++/.
"""

import os
import shutil
import subprocess

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_gxx_fallback() -> None:
    """Builds and runs test_core.cc with the gen_pb_local.py + g++ recipe
    (the docstring contract of that file); rebuilds only when a source is
    newer than the cached binary."""
    import sys

    import glob

    build_dir = os.path.join(REPO, "native", "build-g++")
    os.makedirs(build_dir, exist_ok=True)
    binary = os.path.join(build_dir, "tpuft_test")
    gen_dir = os.path.join(build_dir, "gen")
    # Same source list the bindings' auto-build compiles (minus capi.cc —
    # the test binary has its own main): one tuple, no recipe drift.
    from torchft_tpu._native import NATIVE_SOURCES

    srcs = [os.path.join(REPO, "native", "tests", "test_core.cc")] + [
        os.path.join(REPO, "native", "src", f)
        for f in NATIVE_SOURCES
        if f != "capi.cc"
    ]
    proto = os.path.join(REPO, "proto", "tpuft.proto")
    generator = os.path.join(REPO, "native", "gen_pb_local.py")
    gen_header = os.path.join(gen_dir, "tpuft.pb.h")
    # Regenerate when the proto OR the generator itself is newer than the
    # cached header — an edited codegen must never validate against its
    # own stale output.
    if not os.path.exists(gen_header) or any(
        os.path.getmtime(src) > os.path.getmtime(gen_header)
        for src in (proto, generator)
    ):
        subprocess.run(
            [sys.executable, generator, gen_dir],
            check=True, capture_output=True, timeout=120,
        )
    # Staleness must see headers too (wire.h etc.) and the generated pb —
    # a header-only change rebuilding nothing would green-light a binary
    # that no longer matches the sources under test.
    deps = (
        srcs
        + glob.glob(os.path.join(REPO, "native", "src", "*.h"))
        + [gen_header]
    )
    stale = not os.path.exists(binary) or any(
        os.path.getmtime(s) > os.path.getmtime(binary) for s in deps
    )
    if stale:
        subprocess.run(
            ["g++", "-std=c++17", "-O1", "-I", os.path.join(REPO, "native", "src"),
             "-I", gen_dir, *srcs, "-o", binary, "-lpthread"],
            check=True, capture_output=True, timeout=600,
        )
    out = subprocess.run([binary], capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, f"native suite failed:\n{out.stdout}\n{out.stderr}"


def test_native_core_suite() -> None:
    import torchft_tpu._native  # noqa: F401 — triggers the auto-build

    import pytest

    if shutil.which("ninja") is None or shutil.which("ctest") is None:
        if shutil.which("g++") is None:
            pytest.skip(
                "native suite needs ninja+ctest or g++; none present"
            )
        _run_gxx_fallback()
        return
    build_dir = os.path.join(REPO, "native", "build")
    binary = os.path.join(build_dir, "tpuft_test")
    if not os.path.exists(binary):
        # The library existed before this test ran, so _ensure_built was a
        # no-op; build the full default target set explicitly.
        subprocess.run(["ninja", "-C", build_dir], check=True, capture_output=True)
    out = subprocess.run(
        # No retry: RpcServer/HttpServer now JOIN their connection threads
        # on shutdown (they used to detach, and a detached thread's epilogue
        # racing static destruction SIGABRTed ~1/30 runs at exit).
        ["ctest", "--test-dir", build_dir, "--output-on-failure"],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, f"ctest failed:\n{out.stdout}\n{out.stderr}"
    assert "100% tests passed" in out.stdout


def test_library_is_rebuilt_when_its_sources_change(tmp_path, monkeypatch) -> None:
    """The build is trusted by a digest of its sources stamped beside the
    library, not by the library merely existing: the chip tool copies the
    working tree as it stands, stale build products included."""
    from torchft_tpu import _native

    # The library this process loaded carries the stamp of the sources on disk.
    assert _native._built_from(_native.source_digest())

    root = tmp_path / "repo"
    for rel in ("native/src", "native/tests", "proto"):
        shutil.copytree(os.path.join(REPO, rel), root / rel)
    for rel in ("native/CMakeLists.txt", "native/gen_pb_local.py"):
        shutil.copy(os.path.join(REPO, rel), root / rel)
    lib = root / "torchft_tpu" / "_lib" / "libtpuft.so"
    pb2 = root / "torchft_tpu" / "proto" / "tpuft_pb2.py"
    monkeypatch.setattr(_native, "_REPO_ROOT", str(root))
    monkeypatch.setattr(_native, "_LIB_PATH", str(lib))
    monkeypatch.setattr(_native, "_STAMP_PATH", str(lib.with_suffix(".digest")))
    monkeypatch.setattr(_native, "_PB2_PATH", str(pb2))
    builds = []

    def fake_build() -> None:
        builds.append(_native.source_digest())
        pb2.parent.mkdir(parents=True, exist_ok=True)
        lib.write_bytes(b"")
        pb2.write_text("")

    monkeypatch.setattr(_native, "_build_native", fake_build)
    _native._ensure_built()  # nothing built yet
    _native._ensure_built()  # stamped: trusted
    assert len(builds) == 1
    with open(root / "native" / "src" / "wire.h", "a") as f:
        f.write("// edited\n")
    _native._ensure_built()  # a header changed: the library on disk is stale
    assert len(builds) == 2 and builds[0] != builds[1]
    lib.with_suffix(".digest").unlink()
    _native._ensure_built()  # a library without a stamp is not trusted either
    assert len(builds) == 3
