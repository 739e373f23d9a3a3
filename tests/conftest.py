"""Test config: run everything on a virtual 8-device CPU mesh.

Must set env before jax initializes (SURVEY.md §4: the fake-cluster strategy —
N CPU devices stand in for N TPU cores so placement/sharding logic is tested
without TPU hardware).
"""

import os
import warnings

# Hard override: tests run on virtual CPU devices whatever the environment
# says.
os.environ["JAX_PLATFORMS"] = "cpu"

# Record lock acquisition order across every named_lock in the suite
# (analysis/locks.py): tests/test_analysis.py asserts the union graph is
# acyclic.  Before the package import below so module-level locks record.
os.environ.setdefault("DML_LOCK_ORDER", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Buffer donation is a no-op on CPU; silence the per-call warning.
warnings.filterwarnings(
    "ignore", message=".*buffer donation.*", category=UserWarning
)
# The fused epoch program donates its batch chunks (freed for reuse on
# TPU); on CPU they alias nothing and XLA says so per compile.
warnings.filterwarnings(
    "ignore", message=".*donated buffers were not usable.*",
    category=UserWarning,
)

import pytest  # noqa: E402

try:  # pragma: no cover - env-dependent
    import pytest_timeout  # noqa: F401

    _HAVE_PYTEST_TIMEOUT = True
except ImportError:
    _HAVE_PYTEST_TIMEOUT = False


def pytest_addoption(parser):
    # Fallback registration of pytest-timeout's ini keys so the pyproject
    # `timeout` config parses cleanly on images without the plugin (the CI
    # container; nothing may be pip-installed there).
    if _HAVE_PYTEST_TIMEOUT:
        return
    for name, help_text in (
        ("timeout", "per-test wall-clock ceiling in seconds (fallback "
                    "enforcement: dump stacks and abort the run)"),
        ("timeout_method", "accepted for pytest-timeout compatibility; the "
                           "fallback always uses a watchdog thread"),
    ):
        try:
            parser.addini(name, help_text, default=None)
        except ValueError:  # pragma: no cover - already registered
            pass


def _abort_wedged_test(item, ceiling: float):  # pragma: no cover
    # Loud, with forensics, and terminal: dump every thread's stack (the
    # wedge's location is the whole diagnosis) and end the RUN — the
    # harness then sees a fast nonzero exit instead of a silent hang that
    # eats its 870 s budget.  Mirrors pytest-timeout's "thread" method,
    # including suspending capture first so the dump reaches the real
    # stderr instead of dying in the captured buffer os._exit abandons.
    import faulthandler
    import os
    import sys

    capman = item.config.pluginmanager.getplugin("capturemanager")
    if capman is not None:
        try:
            capman.suspend_global_capture(in_=True)
        except Exception:  # noqa: BLE001 - forensics must not die here
            pass
    sys.stderr.write(
        f"\n\n+++ test ceiling exceeded: {item.nodeid} ran past "
        f"{ceiling:.0f}s — dumping all thread stacks and aborting the "
        f"run +++\n\n"
    )
    sys.stderr.flush()
    faulthandler.dump_traceback(file=sys.stderr)
    sys.stderr.flush()
    os._exit(124)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if _HAVE_PYTEST_TIMEOUT:  # the real plugin owns enforcement
        yield
        return
    import threading

    try:
        ceiling = float(item.config.getini("timeout") or 0)
    except (TypeError, ValueError):
        ceiling = 0.0
    if ceiling <= 0:
        yield
        return
    timer = threading.Timer(
        ceiling, _abort_wedged_test, args=(item, ceiling)
    )
    timer.daemon = True
    timer.start()
    try:
        yield
    finally:
        timer.cancel()


@pytest.fixture(scope="session")
def tmp_results(tmp_path_factory):
    return str(tmp_path_factory.mktemp("results"))
