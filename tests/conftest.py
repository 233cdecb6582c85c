"""Test config: force CPU platform with an 8-device virtual mesh.

Mirrors the reference's test strategy (SURVEY §4): CPU is the reference
backend for correctness, and the virtual 8-device mesh stands in for the
chips when testing sharding/collectives (≙ the reference's local-tracker
simulated cluster, tools/launch.py -n 4 --launcher local).
"""
import os

# Set before JAX is imported: it reads both when its backends initialise.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8")
# The persistent compilation cache stays off for the suite, here and in
# the subprocesses tests start (they inherit the env), so timing and
# isolation do not depend on what an earlier run left in .jax_cache.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

import signal  # noqa: E402

import pytest  # noqa: E402

# Per-test wall-clock cap for `dist`-marked tests (multi-process PS
# launchers): a hung socket/rendezvous must cost one test, not the whole
# tier-1 run.  pytest-timeout isn't a dependency, so this is a plain
# SIGALRM (tests run in the main thread); the launcher subprocesses have
# their own subprocess.run timeouts — this is the backstop above them.
DIST_TEST_TIMEOUT_S = int(os.environ.get("MXNET_TPU_DIST_TEST_TIMEOUT",
                                         "420"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    # ckpt-marked tests spawn kill-and-resume training subprocesses: same
    # hang risk profile as the dist launchers, same backstop
    if (item.get_closest_marker("dist") is None and
            item.get_closest_marker("ckpt") is None) or \
            not hasattr(signal, "SIGALRM"):
        yield
        return

    def _alarm(signum, frame):
        raise TimeoutError(
            f"dist/ckpt test exceeded {DIST_TEST_TIMEOUT_S}s "
            "(MXNET_TPU_DIST_TEST_TIMEOUT) — hung launcher/subprocess?")

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(DIST_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=False)
def seeded():
    import mxnet_tpu as mx
    mx.seed(0)
    yield


@pytest.fixture(autouse=True)
def _drop_published_ps_addresses():
    """An in-process dist_async store publishes its server's address into
    os.environ (kvstore/ps.py publish_address, when there is no
    coordination service).  Left there, the launcher subprocesses of a
    later test in the same worker inherit it and talk to a finished
    test's server instead of their own."""
    yield
    for k in [k for k in os.environ if k.startswith("MXNET_TPU_PS_ADDR_")]:
        del os.environ[k]
