"""Test harness config: force the CPU backend with 8 virtual devices.

This is the moral equivalent of the reference testing its GPU code on an
OpenCL CPU driver and MPI single-process (.travis.yml:15-25,45-59): the
multi-device psum paths run on a virtual 8-device CPU mesh, no TPU pod
needed (SURVEY.md §4).
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


@pytest.fixture(autouse=True, scope="module")
def _bound_compile_accumulation():
    """Drop compiled-executable references at module boundaries.

    XLA:CPU's backend_compile_and_load segfaulted 3/3 full-suite runs at
    the same late test (test_wave_exact_order, ~90% through) on this
    host, while every subset run passes — the crash needs the full
    suite's in-process compile history (~360 tests' worth of live CPU
    executables).  Clearing at module boundaries bounds that
    accumulation; jitted callables recompile transparently on next use.
    Same jaxlib-CPU fragility class as the executable-serialization
    segfault that keeps the persistent compile cache TPU-only
    (lightgbm_tpu/utils/common.py).
    """
    yield
    jax.clear_caches()


# `--dist loadfile` hands a worker one file at a time, in collection order,
# and the longest files sort last by name (test_wave*.py): the run then ends
# on two or three busy workers, a seventh of its time (PR 28: 408 s against
# 350 s of work a worker).  Longest first, as the whole run's junit read;
# every other file keeps its place.
_LONGEST_FIRST = (
    "test_parity_vs_reference.py", "test_wave.py", "test_sparse_store.py",
    "test_wave_exact_order.py", "test_parallel.py",
    "test_python_guide_examples.py", "test_engine.py",
    "test_wave_compact.py", "test_pack.py", "test_obs_spans.py",
    "test_compile_rows.py", "test_bundled_reference.py",
    "test_multiprocess.py", "test_graft_entry.py", "test_fused_iter.py",
    "test_chip_smoke.py", "test_perfbench_correct.py")


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))
