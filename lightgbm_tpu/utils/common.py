"""Small helpers shared across layers (mirrors utils/common.h roles)."""
from __future__ import annotations

import os
from typing import Dict, List

import numpy as np


def array_to_string(arr, high_precision: bool = False) -> str:
    """Space-joined array serialization as Common::ArrayToString renders it."""
    out = []
    for v in arr:
        if isinstance(v, (np.floating, float)):
            if high_precision:
                out.append(repr(float(v)))
            else:
                out.append(_format_double(float(v)))
        else:
            out.append(str(int(v)))
    return " ".join(out)


def _format_double(v: float) -> str:
    # C++ default stream precision is 6 significant digits; the model files
    # round-trip through this.  We keep full precision instead (loaders on
    # both sides parse it fine and it preserves exact re-load equality).
    return repr(v)


def string_to_array(s: str, dtype) -> np.ndarray:
    if not s:
        return np.asarray([], dtype=dtype)
    return np.asarray(s.split(" "), dtype=dtype)


def parse_kv_lines(lines: List[str]) -> Dict[str, str]:
    """key=value lines -> dict (Common::Split on first '=')."""
    out: Dict[str, str] = {}
    for line in lines:
        if "=" in line:
            key, _, val = line.partition("=")
            key = key.strip()
            val = val.strip()
            if key and val:
                out[key] = val
    return out


def avoid_inf(v: float) -> float:
    """Common::AvoidInf — clamp ±inf to ±1e300 for serialization."""
    if np.isnan(v):
        return 0.0
    if v == np.inf:
        return 1e300
    if v == -np.inf:
        return -1e300
    return float(v)


kEpsilon = 1e-15
kMissingValueRange = 1e-20
kMaxTreeOutput = 100.0
kMinScore = -np.inf


def compilation_cache_dir() -> str:
    """The one directory for compiled programs:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    (found from this package's ``__file__``).  A fixed path, because a
    cache directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def enable_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory,
    or None where the cache stays off.  Call before the first compile.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, jax reads the directory from
    the variable itself and this only lowers the thresholds so every
    program is kept, however small or quick to compile (a run reuses
    dozens of small programs besides the grow loop).  Without it the
    cache engages on the TPU backend only, under ``compilation_cache_dir``:
    CPU compiles take seconds, and serializing CPU executables, which
    embed host-specific machine features, has been seen to segfault.
    """
    import jax

    placed = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not placed and jax.default_backend() != "tpu":
        return None
    d = compilation_cache_dir()
    if not placed:
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d
