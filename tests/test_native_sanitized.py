"""Sanitized native builds (ISSUE 5 + ISSUE 15): compile the C
extensions (prep/ed25519c/applyc + the xdrc serializer) with
-fsanitize=address,undefined and run the native differential-oracle
tests under ASan/UBSan in a subprocess; plus the ThreadSanitizer twin —
a `-fsanitize=thread` build under which the ParallelDiffHarness legs
(forced-parallel vs forced-serial vs oracle, seeded) race-check the
GIL-released cluster pthread pool.

Marked `slow` + `sanitize`: tier-1 skips it (the sanitized compile alone
is ~20s, the oracle run minutes); run explicitly with

    python -m pytest tests/test_native_sanitized.py -m sanitize

or via `tools/build_native_sanitized.sh --check` (same machinery; ASan
and TSan builds live in separate dirs — build/sanitized/ vs build/tsan/
— and separate PROCESSES: the runtimes cannot coexist in one).

TSan quirk the helpers encode: the instrumented .so files are BUILT
without LD_PRELOAD (a TSan-preloaded python forking gcc can deadlock in
the runtime's fork interceptor) and only RUN with libtsan preloaded.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.slow, pytest.mark.sanitize]


def _sanitizer_env():
    cc = shutil.which("cc")
    if cc is None:
        pytest.skip("no C compiler")
    libasan = subprocess.run(
        [cc, "-print-file-name=libasan.so"],
        capture_output=True, text=True).stdout.strip()
    if not libasan or not os.path.exists(libasan):
        pytest.skip("cc has no libasan runtime")
    libstdcpp = subprocess.run(
        [cc, "-print-file-name=libstdc++.so"],
        capture_output=True, text=True).stdout.strip()
    env = dict(os.environ)
    env.update({
        "SCT_SANITIZE": "1",
        # libstdc++ must be resolvable when ASan's interceptors
        # initialize or the first C++ throw (JAX/XLA) aborts with
        # "real___cxa_throw != 0"
        "LD_PRELOAD": "%s %s" % (libasan, libstdcpp),
        # CPython deliberately leaks at exit; leak reports would bury
        # the memory-error signal the build exists to catch
        "ASAN_OPTIONS": "detect_leaks=0",
        "JAX_PLATFORMS": "cpu",
    })
    return env


def test_sanitized_build_compiles_all_three_extensions():
    env = _sanitizer_env()
    r = subprocess.run(
        [sys.executable, "-c",
         "from stellar_core_tpu import native\n"
         "assert native.SANITIZE and native._BUILD.endswith('sanitized')\n"
         "assert native.available(), 'prep failed'\n"
         "assert native.ed25519_native() is not None, 'ed25519c failed'\n"
         "assert native.apply_engine() is not None, 'applyc failed'\n"
         "native._compile_xdr_ext()\n"
         "assert native._XDR_MOD is not None, 'xdrc failed'\n"
         "print('SANITIZED-BUILD-OK')"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "SANITIZED-BUILD-OK" in r.stdout
    # any sanitizer finding prints a report on stderr even when the
    # process exits 0 (halt_on_error defaults can vary)
    assert "ERROR: AddressSanitizer" not in r.stderr
    assert "runtime error:" not in r.stderr


def test_native_differential_oracles_pass_under_asan_ubsan():
    """The acceptance gate: the prep/apply/xdr oracle suites — the tests
    that compare every native path against its Python twin — run green
    with the sanitized libraries loaded."""
    env = _sanitizer_env()
    r = subprocess.run(
        [sys.executable, "-m", "pytest",
         "tests/test_native_prep.py", "tests/test_native_apply.py",
         "tests/test_native_xdr.py",
         "-q", "-p", "no:cacheprovider"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=1800)
    tail = (r.stdout or "")[-4000:] + (r.stderr or "")[-4000:]
    assert r.returncode == 0, tail
    assert "ERROR: AddressSanitizer" not in r.stderr, r.stderr[-4000:]
    assert "runtime error:" not in r.stderr, r.stderr[-4000:]


def test_threaded_parallel_close_under_asan_ubsan():
    """ISSUE 13: the conflict-graph parallel close runs worker pthreads
    inside the C engine — data races and heap misuse there are exactly
    what ASan/TSan-class tooling exists to catch. Drive the
    forced-parallel differential legs (parallel-vs-serial-vs-oracle
    equality + the full randomized matrix) under the sanitized build,
    repeatedly enough that the persistent worker pool recycles across
    closes."""
    env = _sanitizer_env()
    r = subprocess.run(
        [sys.executable, "-m", "pytest",
         "tests/test_native_apply.py::test_native_apply_parallel_equality",
         "tests/test_native_apply.py::"
         "test_native_apply_randomized_full_matrix",
         "tests/test_native_apply.py::test_native_apply_all_op_types",
         # ISSUE 32: the order-book index (heap growth, records of
         # offers that died or moved, the refile on rollback) over a
         # 300-offer side and over 2,500
         "tests/test_native_apply.py::"
         "test_index_deep_side_crossed_across_rungs",
         "tests/test_native_apply.py::test_index_rollback_restores_the_side",
         "tests/test_native_apply.py::"
         "test_best_offer_cost_does_not_grow_with_the_side",
         "-q", "-p", "no:cacheprovider"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=1200)
    tail = (r.stdout or "")[-4000:] + (r.stderr or "")[-4000:]
    assert r.returncode == 0, tail
    assert "ERROR: AddressSanitizer" not in r.stderr, r.stderr[-4000:]
    assert "runtime error:" not in r.stderr, r.stderr[-4000:]


# ------------------------------------------------------ ThreadSanitizer leg


def _tsan_lib(name):
    cc = shutil.which("cc")
    if cc is None:
        pytest.skip("no C compiler")
    path = subprocess.run(
        [cc, "-print-file-name=%s" % name],
        capture_output=True, text=True).stdout.strip()
    if not path or not os.path.exists(path):
        pytest.skip("cc has no %s runtime" % name)
    return path


def _tsan_build_env():
    """Environment for BUILDING the TSan extensions: SCT_SANITIZE=thread
    routes native/__init__.py into build/tsan/ with -fsanitize=thread;
    deliberately NO LD_PRELOAD (see module docstring)."""
    env = dict(os.environ)
    env.pop("LD_PRELOAD", None)
    env.update({"SCT_SANITIZE": "thread", "JAX_PLATFORMS": "cpu"})
    return env


def _tsan_run_env():
    """Environment for RUNNING against the prebuilt TSan extensions."""
    libtsan = _tsan_lib("libtsan.so")
    libstdcpp = _tsan_lib("libstdc++.so")
    env = _tsan_build_env()
    env.update({
        "LD_PRELOAD": "%s %s" % (libtsan, libstdcpp),
        # print every report (don't stop at the first); the default
        # nonzero exitcode (66) still fails the subprocess on any
        "TSAN_OPTIONS": "halt_on_error=0",
    })
    return env


def _tsan_prebuild():
    """Build all four TSan-instrumented artifacts without the preload.
    Loading them in THIS (unpreloaded) build step fails by design — the
    artifacts landing in build/tsan/ is the contract."""
    r = subprocess.run(
        [sys.executable, "-c",
         "from stellar_core_tpu import native\n"
         "assert native.SANITIZE_MODE == 'thread', native.SANITIZE_MODE\n"
         "assert native._BUILD.endswith('tsan'), native._BUILD\n"
         "native.available()\n"
         "native.ed25519_native()\n"
         "native.apply_engine()\n"
         "native._compile_xdr_ext()\n"
         "import glob, os\n"
         "for pat in ('libsctprep-*.so', 'libscted25519-*.so',\n"
         "            '_sctapply-*.so', '_sctxdr-*.so'):\n"
         "    assert glob.glob(os.path.join(native._BUILD, pat)), pat\n"
         "print('TSAN-BUILD-OK')"],
        capture_output=True, text=True, cwd=REPO, env=_tsan_build_env(),
        timeout=600)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "TSAN-BUILD-OK" in r.stdout


def test_tsan_build_compiles_and_loads_under_preload():
    _tsan_run_env()          # skip early when no libtsan
    _tsan_prebuild()
    r = subprocess.run(
        [sys.executable, "-c",
         "from stellar_core_tpu import native\n"
         "assert native.apply_engine() is not None, 'applyc failed'\n"
         "assert native.available(), 'prep failed'\n"
         "assert native.ed25519_native() is not None, 'ed25519c failed'\n"
         "print('TSAN-LOAD-OK')"],
        capture_output=True, text=True, cwd=REPO, env=_tsan_run_env(),
        timeout=600)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "TSAN-LOAD-OK" in r.stdout
    assert "WARNING: ThreadSanitizer" not in r.stderr, r.stderr[-4000:]


def test_threaded_parallel_close_under_tsan():
    """THE race gate (ISSUE 15 acceptance): the ParallelDiffHarness —
    forced-parallel vs forced-serial vs Python-oracle equality plus the
    seeded randomized conflict mixes (2 seeds) — runs with the
    GIL-released cluster pthread pool fully TSan-instrumented, with
    zero unsuppressed ThreadSanitizer reports. TSan's own nonzero exit
    (66) on any report fails the run even if pytest passed."""
    env = _tsan_run_env()
    _tsan_prebuild()
    r = subprocess.run(
        [sys.executable, "-m", "pytest",
         "tests/test_native_apply.py::test_native_apply_parallel_equality",
         "tests/test_native_apply.py::test_native_apply_parallel_seeded",
         "-q", "-p", "no:cacheprovider"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=1800)
    tail = (r.stdout or "")[-4000:] + (r.stderr or "")[-4000:]
    assert r.returncode == 0, tail
    assert "WARNING: ThreadSanitizer" not in r.stderr, r.stderr[-6000:]
    assert "3 passed" in r.stdout, tail


def test_order_book_index_under_tsan():
    """ISSUE 32: the price index of a book side takes no lock, because a
    close with an order-book op applies on one thread with the GIL held.
    The deep-book differential cases (a 300-offer side crossed over
    several rungs, every rollback of an indexed offer, a revoke in the
    middle of a close, the seeded matrix over 200-offer sides) run under
    ThreadSanitizer beside the engine's worker pool, which the static
    closes of their set-up ledgers start: zero reports."""
    env = _tsan_run_env()
    _tsan_prebuild()
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/test_native_apply.py",
         "-k", "test_index or deep_books",
         "-q", "-p", "no:cacheprovider"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=1800)
    tail = (r.stdout or "")[-4000:] + (r.stderr or "")[-4000:]
    assert r.returncode == 0, tail
    assert "WARNING: ThreadSanitizer" not in r.stderr, r.stderr[-6000:]
    assert "20 passed" in r.stdout, tail
