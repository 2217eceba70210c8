"""Drive the native C++ unit tests (plain + sanitizers) from pytest.

Reference discipline: the Go master runs `go test -race -short`
(master/Makefile:187). Here the same sources build plain and under
ThreadSanitizer / AddressSanitizer+UBSan:

  - test_native_units:  `make -C native test` — pure-logic units plus the
    threaded master test (real Master hammered through handle() from many
    threads), no sanitizer.
  - test_native_tsan / test_native_asan: the fast pure-logic binary under
    each sanitizer; builds are skipped cleanly when the toolchain cannot
    produce sanitized binaries (no libtsan/libasan).
  - test_master_threads_tsan (slow): the full threaded master under TSan —
    the `go test -race` analogue. Needs tests/tsan_clockwait_shim.cc:
    without it this toolchain's libtsan misses pthread_cond_clockwait
    (libstdc++ steady-clock condition_variable waits) and corrupts its
    lock bookkeeping into bogus "double lock" reports.
"""

import functools
import os
import subprocess
import tempfile

import pytest
from conftest import NATIVE, native_build_lock

REPO = os.path.dirname(NATIVE)


def _make(*args: str, timeout: int = 600) -> subprocess.CompletedProcess:
    with native_build_lock():
        return subprocess.run(
            ["make", "-C", NATIVE, f"-j{os.cpu_count()}", *args],
            capture_output=True, text=True, timeout=timeout,
        )


def _run(binary: str, env=None) -> subprocess.CompletedProcess:
    e = dict(os.environ)
    e.update(env or {})
    return subprocess.run(
        [os.path.join(NATIVE, "bin", binary)],
        capture_output=True, text=True, timeout=300, env=e,
    )


@functools.lru_cache(maxsize=None)
def _sanitizer_available(flag: str) -> bool:
    """Can the toolchain link a -fsanitize=<flag> binary?"""
    with tempfile.TemporaryDirectory() as d:
        src = os.path.join(d, "probe.cc")
        with open(src, "w") as f:
            f.write("int main() { return 0; }\n")
        r = subprocess.run(
            [os.environ.get("CXX", "g++"), f"-fsanitize={flag}", "-o",
             os.path.join(d, "probe"), src],
            capture_output=True, timeout=120,
        )
        return r.returncode == 0


def test_native_units():
    r = _make("test")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "0 failures" in r.stdout


def test_each_source_is_compiled_once_for_every_binary(native_binaries):
    """The build is by objects: built, `make all` has nothing to do; a
    touched `common/trace.cc` (which master and agent both hold) is one
    compile, and the rest is links."""
    r = _make("-q", "all")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    r = _make("-n", "-W", "common/trace.cc", "all")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    cxx = os.environ.get("CXX", "g++")
    steps = [ln for ln in r.stdout.splitlines() if ln.startswith(cxx + " ")]
    compiles = [ln for ln in steps if " -c " in ln]
    assert len(compiles) == 1 and compiles[0].endswith(
        " -c -o bin/obj/common/trace.o common/trace.cc"), r.stdout
    links = [ln.split(" -o ")[1].split()[0] for ln in steps
             if ln not in compiles]
    assert sorted(links) == ["bin/determined-agent",
                             "bin/determined-master"], r.stdout
    assert not [ln for ln in steps if ".cc" in ln and ln not in compiles]


def _sanitized_unit(flag: str, binary: str, env=None):
    if not _sanitizer_available(flag):
        pytest.skip(f"toolchain cannot build -fsanitize={flag} binaries")
    r = _make(f"bin/{binary}")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = _run(binary, env=env)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "0 failures" in out.stdout


def test_native_tsan():
    _sanitized_unit("thread", "test_native_tsan")


def test_native_asan():
    _sanitized_unit("address", "test_native_asan")


@pytest.mark.slow
def test_master_threads_tsan():
    """The go-test -race analogue: real master, many concurrent clients,
    under ThreadSanitizer (with the pthread_cond_clockwait shim)."""
    _sanitized_unit("thread", "test_master_threads_tsan",
                    env={"TSAN_OPTIONS": "halt_on_error=1"})


# ---------------------------------------------------------------------------
# compile-time thread-safety gate (`make -C native tsa`,
# docs/static-analysis.md) — mirrors the sanitizer probes: runs for real
# when a thread-safety-capable clang is installed, skips cleanly otherwise.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _tsa_clang() -> str:
    """Path/name of a clang++ that understands -Wthread-safety, or ''."""
    cxx = os.environ.get("CLANGXX", "clang++")
    try:
        r = subprocess.run(
            [cxx, "-x", "c++", "-fsyntax-only", "-Werror",
             "-Wthread-safety", "-"],
            input="int main() { return 0; }\n",
            capture_output=True, text=True, timeout=120,
        )
    except (OSError, subprocess.TimeoutExpired):
        return ""
    return cxx if r.returncode == 0 else ""


def test_tsa_target_never_breaks_the_build():
    """`make tsa` must exit 0 on toolchains without clang (it prints a
    skip notice) — it is folded into `make lint`, which has to stay
    runnable everywhere."""
    r = _make("tsa")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert ("thread-safety gate skipped" in r.stdout
            or "gate skipped" in r.stdout
            or "-Wthread-safety -Werror over native/" in r.stdout)


def test_tsa_gate_compiles_native_clean():
    """With a capable clang, the whole native layer passes
    -Wthread-safety -Werror (the annotation contract holds)."""
    if not _tsa_clang():
        pytest.skip("no clang++ with -Wthread-safety support installed")
    r = _make("tsa")
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "gate skipped" not in r.stdout


_TSA_VIOLATION = """\
#include "common/mutex.h"
#include "common/thread_annotations.h"

class Counter {
 public:
  void bump() { ++n_; }  // BUG: reads/writes n_ without holding mu_

 private:
  det::Mutex mu_;
  int n_ GUARDED_BY(mu_) = 0;
};

int main() {
  Counter c;
  c.bump();
  return 0;
}
"""

_TSA_CORRECT = _TSA_VIOLATION.replace(
    "void bump() { ++n_; }  // BUG: reads/writes n_ without holding mu_",
    "void bump() { det::MutexLock lock(mu_); ++n_; }")


def _tsa_compile(source: str) -> subprocess.CompletedProcess:
    cxx = _tsa_clang()
    with tempfile.TemporaryDirectory() as d:
        src = os.path.join(d, "probe.cc")
        with open(src, "w") as f:
            f.write(source)
        return subprocess.run(
            [cxx, "-std=c++17", "-fsyntax-only", "-Wthread-safety",
             "-Werror", "-I", NATIVE, src],
            capture_output=True, text=True, timeout=120,
        )


def test_tsa_gate_fails_on_seeded_violation():
    """The gate is not vacuous: a TU that touches a GUARDED_BY field
    without the mutex FAILS to compile, and the same TU with a MutexLock
    compiles clean (so the failure is the analysis, not the harness)."""
    if not _tsa_clang():
        pytest.skip("no clang++ with -Wthread-safety support installed")
    bad = _tsa_compile(_TSA_VIOLATION)
    assert bad.returncode != 0, "seeded GUARDED_BY violation compiled clean"
    assert "-Wthread-safety" in bad.stderr or "guarded by" in bad.stderr, \
        bad.stderr[-3000:]
    good = _tsa_compile(_TSA_CORRECT)
    assert good.returncode == 0, good.stderr[-3000:]


# ---------------------------------------------------------------------------
# native_lint (NL001-NL005) — the textual half of the gate; runs on every
# toolchain. Synthetic trees prove each rule is non-vacuous; the real tree
# must be clean (the dogfood assertion `make lint` enforces).
# ---------------------------------------------------------------------------

from determined_tpu.analysis import native_lint  # noqa: E402


def _tree(tmp_path, files):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return str(tmp_path)


class TestNativeLint:
    def test_real_tree_is_clean(self):
        assert native_lint.lint_native(REPO) == []

    def test_real_tree_escape_budget(self):
        assert native_lint.tsa_escape_count(REPO) <= \
            native_lint.MAX_TSA_ESCAPES

    def test_nl001_locked_without_requires(self, tmp_path):
        root = _tree(tmp_path, {"native/master/x.h": (
            "class M {\n"
            "  void grow_locked(int n);\n"
            "};\n")})
        probs = native_lint._check_locked_requires(root)
        assert len(probs) == 1 and "NL001" in probs[0] \
            and "grow_locked" in probs[0]

    def test_nl001_negative_with_requires(self, tmp_path):
        root = _tree(tmp_path, {"native/master/x.h": (
            "class M {\n"
            "  void grow_locked(int n) REQUIRES(mu_);\n"
            "};\n")})
        assert native_lint._check_locked_requires(root) == []

    def test_nl001_cc_free_function(self, tmp_path):
        root = _tree(tmp_path, {"native/agent/y.cc": (
            "static void settle_locked() {\n"
            "}\n"
            "void caller() {\n"
            "  settle_locked();\n"  # indented call site: not flagged
            "}\n")})
        probs = native_lint._check_locked_requires(root)
        assert len(probs) == 1 and "settle_locked" in probs[0]

    def test_nl002_unguarded_field(self, tmp_path):
        hdr = (
            "class M {\n"
            "  Mutex mu_;\n"
            "  int counter_;\n"
            "};\n")
        root = _tree(tmp_path, {
            "native/master/master.h": hdr,
            "native/master/rm.h": "// empty\n"})
        probs = native_lint._check_guarded_fields(root)
        assert any("NL002" in p and "counter_" in p for p in probs)

    def test_nl002_negative_guarded_or_justified(self, tmp_path):
        hdr = (
            "class M {\n"
            "  Mutex mu_;\n"
            "  int counter_ GUARDED_BY(mu_);\n"
            "  std::atomic<bool> running_{false};\n"
            "  int cfg_port_;  // not-guarded: set once before start()\n"
            "};\n"
            "class NoLock {\n"
            "  int free_field_;\n"  # class without a Mutex: no discipline
            "};\n")
        root = _tree(tmp_path, {
            "native/master/master.h": hdr,
            "native/master/rm.h": "// empty\n"})
        assert native_lint._check_guarded_fields(root) == []

    def test_nl003_unjustified_escape(self, tmp_path):
        root = _tree(tmp_path, {"native/master/z.cc": (
            "void weird() NO_THREAD_SAFETY_ANALYSIS {\n"
            "}\n")})
        probs, count = native_lint._check_tsa_escapes(root)
        assert count == 1
        assert len(probs) == 1 and "NL003" in probs[0]

    def test_nl003_justified_but_over_budget(self, tmp_path):
        body = ("// tsa: justified for the test\n"
                "void weird() NO_THREAD_SAFETY_ANALYSIS {}\n") * 4
        root = _tree(tmp_path, {"native/master/z.cc": body})
        probs, count = native_lint._check_tsa_escapes(root)
        assert count == 4
        assert len(probs) == 1 and "budget" in probs[0]

    def test_nl004_fault_registry_both_directions(self, tmp_path):
        files = {
            "native/master/m.cc": 'x = FAULT_POINT("a.b");\n',
            "native/common/faultpoint.cc": (
                '    {"a.b", "master", "x"},\n'
                '    {"stale.row", "master", "y"},\n'),
            "docs/chaos.md": "| `a.b` | x |\n| `ghost.point` | y |\n",
        }
        for rel in native_lint.PY_FAULT_SOURCES:
            files[rel] = "# nothing\n"
        probs = native_lint._check_fault_registry(_tree(tmp_path, files))
        assert any("stale.row" in p and "no FAULT_POINT call site" in p
                   for p in probs)
        assert any("ghost.point" in p and "stale row" in p for p in probs)
        assert any("stale.row" in p and "not documented" in p
                   for p in probs)

    def test_nl005_route_drift_both_directions(self, tmp_path):
        spec = {"paths": {"/api/v1/experiments": {},
                          "/api/v1/ghosts/{id}": {}}}
        import json as _json
        root = _tree(tmp_path, {
            "native/master/master.cc": (
                'if (root == "experiments") {}\n'
                'if (root == "agents") {}\n'),
            "proto/openapi.json": _json.dumps(spec),
        })
        probs = native_lint._check_routes(root)
        assert any("'agents'" in p and "absent from the OpenAPI" in p
                   for p in probs)
        assert any("'ghosts'" in p and "not dispatched" in p for p in probs)
