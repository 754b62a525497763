"""Entry-point invariants the chip runs depend on: where the persistent
compile cache goes (utils/common.py enable_compilation_cache), and that
importing the package touches no JAX backend.  (That bench.py and
chip_smoke.py refuse the CPU backend is pinned in tests/test_chip_smoke.py.)
"""
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def test_compilation_cache_placed_from_outside(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, jax reads the directory from
    the variable itself: enable_compilation_cache() never updates
    jax_compilation_cache_dir, only lowers the thresholds, returns the
    variable's directory, and a fresh jit leaves entries there (on any
    backend: the operator who set the variable chose it)."""
    code = (
        "import jax, os\n"
        "updates = []\n"
        "real = jax.config.update\n"
        "def spy(name, value):\n"
        "    updates.append(name)\n"
        "    real(name, value)\n"
        "jax.config.update = spy\n"
        "from lightgbm_tpu.utils.common import enable_compilation_cache\n"
        "d = enable_compilation_cache()\n"
        "assert d == os.environ['JAX_COMPILATION_CACHE_DIR'], d\n"
        "assert sorted(updates) == [\n"
        "    'jax_persistent_cache_min_compile_time_secs',\n"
        "    'jax_persistent_cache_min_entry_size_bytes'], updates\n"
        "import jax.numpy as jnp\n"
        "jax.jit(lambda x: (x @ x).sum())(jnp.ones((128, 128)))"
        ".block_until_ready()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert len(list(tmp_path.iterdir())) > 0


def _recorded_cache_call(monkeypatch, backend):
    """enable_compilation_cache() with the variable unset and the backend
    reported as `backend`; config updates are recorded, not applied (the
    cache must not engage for the rest of this CPU test process)."""
    import jax
    from lightgbm_tpu.utils import common
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    updates, made = {}, []
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    monkeypatch.setattr(common.os, "makedirs",
                        lambda d, exist_ok=False: made.append(d))
    return common.enable_compilation_cache(), updates, made


def test_compilation_cache_off_on_cpu(monkeypatch):
    """Variable unset, CPU backend: the cache stays off.  Serializing
    host-feature-specific CPU executables has segfaulted, and CPU
    compiles take seconds."""
    d, updates, made = _recorded_cache_call(monkeypatch, "cpu")
    assert d is None and not updates and not made


def test_compilation_cache_in_the_checkout_on_tpu(monkeypatch):
    """Variable unset, TPU backend: <checkout>/.jax_cache, a fixed path
    found from the package's __file__ (and listed in .gitignore)."""
    d, updates, made = _recorded_cache_call(monkeypatch, "tpu")
    assert d == os.path.join(REPO, ".jax_cache")
    assert made == [d]
    assert updates == {"jax_compilation_cache_dir": d,
                       "jax_persistent_cache_min_entry_size_bytes": -1,
                       "jax_persistent_cache_min_compile_time_secs": 0.0}
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_package_import_is_backend_clean():
    """`import lightgbm_tpu` must touch no JAX backend: the tools whose
    parent runs one child per arm (tools/bench_suite.py,
    tools/parity_flagship.py, __graft_entry__.py) import the package in
    the parent, and a parent that has initialized the default backend
    holds the chip its children need.

    Probed via a public signal: with JAX_PLATFORMS set to a nonexistent
    platform, backend initialization raises — so the import only
    succeeds while it touches no backend."""
    code = (
        "import lightgbm_tpu\n"
        "print('clean')\n")
    env = dict(os.environ, JAX_PLATFORMS="nonexistent_platform")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "clean" in r.stdout
