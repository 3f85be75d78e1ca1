import os
import signal
import threading

import jax
import pytest

from repro.configs.tiny import config as tiny_config
from repro.data.math_task import MathTask
from repro.models import model as M
from repro.sharding import tree_values

# ---------------------------------------------------------------------------
# per-test timeout: use pytest-timeout when installed (CI), else fall back
# to a SIGALRM watchdog so a hung event loop / chaos test fails loudly
# instead of wedging the whole suite. The fallback only arms on the main
# thread of a platform that has SIGALRM (i.e. not Windows).
# ---------------------------------------------------------------------------

_TIMEOUT_S = float(os.environ.get("PYTEST_PER_TEST_TIMEOUT", "300"))

try:
    import pytest_timeout  # noqa: F401
    _HAVE_PYTEST_TIMEOUT = True
except ImportError:
    _HAVE_PYTEST_TIMEOUT = False


def pytest_configure(config):
    if _HAVE_PYTEST_TIMEOUT and config.getoption("timeout", None) is None \
            and not config.getini("timeout"):
        config.option.timeout = _TIMEOUT_S
    config.addinivalue_line(
        "markers", "dryrun: exercises the CLI dry-run path")
    config.addinivalue_line(
        "markers", "slow: long multi-stage system test")


# ---------------------------------------------------------------------------
# skip hygiene: every skip in this suite must name a reason on the
# allowlist below. Conditions that are *permanent* (an arch that cannot
# take a code path by construction) belong in the parametrization, not in
# runtime skips; what remains is exactly the optional-dependency gates,
# which CI installs and runs. A skip with any other reason fails the run
# so dead tests can't hide behind an unexplained `pytest.skip`.
# ---------------------------------------------------------------------------

_ALLOWED_SKIP_REASONS = (
    # property suites: hypothesis is absent from the slim CPU image and
    # installed in CI (test_algo, test_attention_variants, test_packing,
    # test_paged_cache, test_sim, test_substrate)
    "could not import 'hypothesis'",
    # real-mesh runtime suite (test_mesh_runtime): XLA fixes the device
    # count at backend init, so the default single-device run skips it;
    # CI's multi-device job re-runs the suite with
    # XLA_FLAGS=--xla_force_host_platform_device_count=8
    "needs 8 devices",
    # TPU compile suite (test_tpu_compile): compiles against a described
    # v5e, which needs the TPU compiler library that ships with jax[tpu]
    "no v5e:2x2 topology can be described here",
)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.skipped and not item.get_closest_marker("skip"):
        lr = rep.longrepr
        reason = lr[2] if isinstance(lr, tuple) else str(lr)
        if not any(pat in reason for pat in _ALLOWED_SKIP_REASONS):
            rep.outcome = "failed"
            rep.longrepr = (
                f"unexplained skip: {reason!r} — either fix the test, "
                f"exclude the case at parametrize time, or add the reason "
                f"to _ALLOWED_SKIP_REASONS in tests/conftest.py")


if not _HAVE_PYTEST_TIMEOUT:
    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        can_alarm = (hasattr(signal, "SIGALRM") and _TIMEOUT_S > 0
                     and threading.current_thread()
                     is threading.main_thread())
        if not can_alarm:
            yield
            return

        def _on_alarm(signum, frame):
            raise TimeoutError(
                f"test exceeded {_TIMEOUT_S:.0f}s "
                f"(PYTEST_PER_TEST_TIMEOUT fallback watchdog)")

        prev = signal.signal(signal.SIGALRM, _on_alarm)
        signal.setitimer(signal.ITIMER_REAL, _TIMEOUT_S)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, prev)


@pytest.fixture(scope="session")
def task():
    return MathTask(max_operand=5, ops="+")


@pytest.fixture(scope="session")
def tiny_cfg(task):
    return tiny_config(vocab_size=task.tok.vocab_size)


@pytest.fixture(scope="session")
def tiny_params(tiny_cfg):
    return tree_values(M.init_params(tiny_cfg, jax.random.PRNGKey(0)))
