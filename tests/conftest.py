"""Test config: run on a virtual 8-device CPU mesh (the reference tests
distributed logic with single-host multi-process CPU/Gloo, SURVEY.md §4; we
use XLA's host-platform device-count flag instead)."""
import os

# Hard-set (not setdefault): whatever platform the machine environment
# names, unit tests run on the virtual 8-device CPU mesh — multi-chip
# coverage without multi-chip hardware, and no test ever opens a chip that
# belongs to one process at a time.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Tight numeric comparisons vs numpy references (TPU prod keeps the default
# bf16-friendly matmul precision).
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")

import pytest  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture
def checker():
    """Enable the global lock-order checker for the test, leave it clean
    after — restoring (not clobbering) a session-wide
    PADDLE_TPU_LOCKCHECK=1. Shared by test_lockcheck.py (FSM units) and
    test_batching.py (pool lock discipline)."""
    from paddle_tpu.analysis import lockcheck

    was_enabled = lockcheck.enabled()
    lockcheck.enable()
    lockcheck.reset()
    yield lockcheck
    lockcheck.reset()
    if not was_enabled:
        lockcheck.disable()


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' (ROADMAP.md): the mark fences
    # heavyweight coverage (subprocess smokes etc.) out of the CI budget
    config.addinivalue_line(
        "markers", "slow: heavyweight test excluded from the tier-1 run")


# Tier-1 budget ordering: the suite brushes its CI wall-clock timeout, and
# a timeout truncates whatever happens to sort LAST alphabetically — i.e.
# whole subsystems' cheap unit coverage — while these multi-process
# integration sweeps burn minutes for a handful of tests early in the
# alphabet. Collect them at the END instead: every fast test keeps running
# inside the budget, and when the clock does run out it truncates the
# slowest integration tail first (each of these files is also exercised by
# its subsystem's unit tests and the fault-injection harnesses). Ordering
# is file-level and stable, so fixtures and in-file dependencies are
# untouched.
_WALL_CLOCK_TAIL = (
    "test_decode_engine.py",      # ~30s / 17 tests (AOT decode buckets)
    "test_engine_pipeline.py",    # ~13s / 18 tests (multi-step dispatch)
    "test_vision_zoo_r3.py",      # ~110s / 9 tests (zoo fwd+grad sweeps)
    "test_launch.py",             # ~50s /  9 tests (elastic relaunch)
    "test_examples.py",           # ~67s / 11 example subprocesses
    "test_serving_fault_injection.py",  # ~90s / 1 test (22 fault phases)
    "test_train_fault_injection.py",  # ~45s / 1 test (6 faulted runs)
    "test_multiprocess_dist.py",  # ~10s /  1 test  (spawned world)
    "test_multiprocess_hybrid.py",  # all 3 hybrid jobs slow-marked (PR 17)
)


def pytest_collection_modifyitems(config, items):
    order = {name: i for i, name in enumerate(_WALL_CLOCK_TAIL)}
    items.sort(key=lambda it: order.get(it.fspath.basename, -1))
