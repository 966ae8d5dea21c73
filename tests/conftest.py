"""Test harness configuration.

- Pins JAX to a virtual 8-device CPU mesh (multi-chip sharding tests run
  without TPU hardware), per the project build contract. JAX_PLATFORMS is
  assigned, not defaulted: a test run can never take the chip.
- Places the compile cache by the one rule
  (parallel.device.configure_compile_cache): JAX_COMPILATION_CACHE_DIR
  where it is set, `<repo>/.jax_cache` otherwise.
- Reseeds the deterministic global RNG before every test, mirroring the
  reference's Catch listener (src/test/test.cpp:47-68).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=8").strip()

from stellar_core_tpu.parallel.device import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--protocol-version", type=int, default=None, metavar="N",
        help="Re-run the suite with TestLedger/app genesis at protocol N "
             "(9..13) — the reference's --all-versions re-run "
             "(src/test/test.cpp:213-217). Tests marked "
             "min_version(M)/max_version(M) outside N's range are "
             "skipped; tests pinning explicit versions are unaffected.")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "min_version(n): behavior needs protocol >= n; skipped "
        "when --protocol-version is lower")
    config.addinivalue_line(
        "markers", "max_version(n): behavior gone after protocol n; "
        "skipped when --protocol-version is higher")
    v = config.getoption("--protocol-version")
    if v is not None:
        from stellar_core_tpu import testing as _testing
        from stellar_core_tpu.main.config import Config as _Config
        _testing.DEFAULT_LEDGER_VERSION = v
        _Config.LEDGER_PROTOCOL_VERSION = v


def pytest_runtest_setup(item):
    v = item.config.getoption("--protocol-version")
    if v is None:
        return
    lo = item.get_closest_marker("min_version")
    if lo is not None and v < lo.args[0]:
        pytest.skip("needs protocol >= %d, running at %d" % (lo.args[0], v))
    hi = item.get_closest_marker("max_version")
    if hi is not None and v > hi.args[0]:
        pytest.skip("behavior <= protocol %d, running at %d"
                    % (hi.args[0], v))


@pytest.fixture(autouse=True)
def _reseed_rng():
    from stellar_core_tpu.util import rnd
    rnd.reseed(0xFEEDFACE)
    yield


@pytest.fixture(autouse=True)
def _thread_discipline():
    """Arm the runtime thread-discipline checks (util/threads.py) for the
    whole run: `@main_thread_only` affinity asserts and the lock-order
    checker are live in every tier-1 test, binding the pytest thread as
    THE main/consensus thread (it is the thread that cranks every
    VirtualClock). Re-armed per test so a test that rebinds or disarms
    can't leak state."""
    from stellar_core_tpu.util import threads
    threads.arm()
    yield
    threads.disarm()
