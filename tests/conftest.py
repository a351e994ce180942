"""Test harness configuration.

Mirrors the reference's strategy of exercising distributed logic on a single
node (``apex/transformer/testing/distributed_test_base.py:22-60`` spawns one
process per GPU): here a single process gets 8 virtual CPU devices via
``--xla_force_host_platform_device_count`` (SURVEY.md §4 implication), and
Pallas kernels run in interpreter mode where exercised.

Set ``APEX_TPU_TEST_TPU=1`` to run the suite on a real TPU backend instead
(through the chip tool; one process holds the chip). In that mode
``APEX_TPU_FORCE_PALLAS=tpu`` is pinned before any test module imports:
several modules ``setdefault`` it to ``interpret`` at import, which on the
chip would quietly interpret the kernels the run exists to compile.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import after env setup)

TPU_MODE = os.environ.get("APEX_TPU_TEST_TPU", "0") == "1"
#: what "run the Pallas kernel" means in this run: the interpreter on
#: CPU, the Mosaic-compiled kernel on the chip
KERNEL_MODE = "tpu" if TPU_MODE else "interpret"

if not TPU_MODE:
    jax.config.update("jax_platforms", "cpu")
else:
    os.environ["APEX_TPU_FORCE_PALLAS"] = "tpu"
    # numerics tests were written against true-fp32 math; TPU's default
    # matmul precision multiplies fp32 operands in bf16 passes (~4e-3
    # relative error), which is a precision POLICY, not a kernel bug —
    # force full fp32 so CPU-calibrated tolerances hold on hardware
    jax.config.update("jax_default_matmul_precision", "highest")

import gc  # noqa: E402

import pytest  # noqa: E402

# The suite holds thousands of compiled XLA programs by the time the later
# files run, and jax's allocation churn makes CPython run full (gen-2)
# collections constantly — each one scanning the whole ever-growing heap.
# Measured effect: the same serving test takes 2-3x longer at the 80% mark
# of a full run than in isolation. Periodically promoting survivors to the
# GC's permanent generation keeps collections scanning only recent objects;
# long-lived executables/caches were never collectable garbage anyway.
_GC_FREEZE_EVERY = 25
_tests_run = 0


def pytest_collection_finish(session):
    gc.collect()
    gc.freeze()


def pytest_runtest_teardown(item, nextitem):
    global _tests_run
    _tests_run += 1
    if nextitem is not None and nextitem.module is not item.module:
        # a finished module's compiled programs are dead weight that jax's
        # caches keep alive; dropping them at the module boundary took the
        # tier-1 suite from ~780-860 s to ~685 s on this box (PR 21)
        jax.clear_caches()
        gc.collect()
        gc.freeze()
    elif _tests_run % _GC_FREEZE_EVERY == 0:
        gc.collect()
        gc.freeze()


@pytest.fixture
def pallas_kernels(monkeypatch):
    """Dispatch the fused ops to their Pallas kernels for this test —
    interpreted on CPU, compiled by Mosaic under ``APEX_TPU_TEST_TPU=1``
    (for modules that otherwise pin the ``jnp`` reference path)."""
    from apex_tpu.ops import _support

    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", KERNEL_MODE)
    _support.pallas_mode.cache_clear()
    yield
    _support.pallas_mode.cache_clear()


@pytest.fixture
def mesh8():
    """A (2 data, 2 pipeline, 1 context, 2 tensor) mesh over 8 devices."""
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2, pipeline_model_parallel_size=2)
    yield mesh
    parallel_state.destroy_model_parallel()


@pytest.fixture
def data_mesh():
    """Pure data-parallel mesh over all 8 devices."""
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel()
    yield mesh
    parallel_state.destroy_model_parallel()


def _skip_if_undersized_mesh(excinfo):
    """On backends with fewer than 8 devices (the real single-chip TPU
    under APEX_TPU_TEST_TPU=1), a mesh request the hardware cannot satisfy
    is a SKIP, not a failure — the same tests run for real on the 8-device
    virtual CPU mesh. Anchored on the dedicated exception TYPE (ADVICE r2:
    message-substring anchors would also mask genuine mesh-construction
    regressions, e.g. num_slices divisibility errors)."""
    from apex_tpu.transformer.parallel_state import UndersizedMeshError

    if (isinstance(excinfo, UndersizedMeshError)
            and len(jax.devices()) < 8):
        pytest.skip(f"multi-device test on a {len(jax.devices())}-device "
                    f"backend: {excinfo}")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    try:
        return (yield)
    except RuntimeError as e:
        _skip_if_undersized_mesh(e)
        raise


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    # mesh fixtures (mesh8/data_mesh) raise during setup
    try:
        return (yield)
    except RuntimeError as e:
        _skip_if_undersized_mesh(e)
        raise
