"""Fused boosting iteration (ops/fused_iter.py).

* bit-identity: with ``tpu_fused_iter=on`` the single-entry program
  reproduces the staged chain's model file and predictions bit for bit —
  on the default (exact) grower AND on the CPU-interpret Pallas wave
  path (``tpu_pallas_interpret=true``) across scaled-down versions of
  the flagship/epsilon/msltr/expo_cat/multiclass benchmark shape buckets.
* eligibility: DART/GOSS/custom-fobj/gradient-health configs fall back
  to the staged chain (with a warning under ``on``), multiclass fuses
  (the class step, tests/test_fused_multiclass.py), and
  ``auto`` keeps the staged chain on plain-CPU default runs.
* the default boosting loop issues ZERO mid-tree host syncs — every
  deliberate block routes through obs/timers.fence, whose counter is
  the audit (the async-dispatch contract both paths rely on).
* band-probe regression: `tile_plan_vmem_report` (ops/pallas_wave.py)
  reproduces and fixes the former 18-30 MB band degeneracy the fused
  probe work root-caused.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.fused_iter import fused_supported
from lightgbm_tpu.obs import timers as obs_timers


def _xy(n, f, seed, classification=True, num_class=2):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    raw = X[:, 0] - 0.5 * X[:, 1 % f] + 0.1 * rng.standard_normal(n)
    if num_class > 2:   # the class whose column is largest
        return X, np.argmax(X[:, :num_class], axis=1).astype(np.float32)
    y = (raw > 0).astype(np.float32) if classification \
        else raw.astype(np.float32)
    return X, y


def _pair(params, X, y, rounds):
    """Train the same data fused and staged; return both boosters."""
    pf = dict(params, tpu_fused_iter="on")
    ps = dict(params, tpu_fused_iter="off")
    bf = lgb.train(pf, lgb.Dataset(X, label=y, params=pf),
                   num_boost_round=rounds)
    bs = lgb.train(ps, lgb.Dataset(X, label=y, params=ps),
                   num_boost_round=rounds)
    return bf, bs


def _assert_identical(bf, bs, X):
    assert bf._gbdt._fused_state[0] is not None, \
        "tpu_fused_iter=on did not resolve to the fused program"
    assert bs._gbdt._fused_state[0] is None
    assert bf.model_to_string() == bs.model_to_string()
    np.testing.assert_array_equal(bf.predict(X), bs.predict(X))


# ------------------------------------------------------------ bit identity

def test_fused_matches_staged_default_growth():
    X, y = _xy(500, 12, 0)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1}
    bf, bs = _pair(p, X, y, rounds=8)
    _assert_identical(bf, bs, X)


def test_fused_matches_staged_regression_objective():
    X, y = _xy(400, 8, 1, classification=False)
    p = {"objective": "regression", "num_leaves": 7,
         "min_data_in_leaf": 5, "verbose": -1}
    bf, bs = _pair(p, X, y, rounds=6)
    _assert_identical(bf, bs, X)


# scaled-down benchmark shape buckets (tools/BENCH_SUITE.md): the axes
# that select different wave-kernel layouts — narrow-F (flagship),
# wide-F (epsilon), mid-F deep trees (msltr), and the pallas_ct fused
# partition kernel (expo_cat's ct-bound shape), and seven class trees an
# iteration through the same kernel (covtype's class step)
PARITY_MATRIX = [
    ("flagship", 400, 12, 15, "pallas_t", "binary"),
    ("epsilon", 260, 48, 15, "pallas_t", "binary"),
    ("msltr", 350, 24, 31, "pallas_t", "regression"),
    ("expo_cat", 300, 10, 7, "pallas_ct", "binary"),
    ("multiclass", 350, 12, 7, "pallas_ct", "multiclass"),
]


@pytest.mark.parametrize("name,n,f,leaves,mode,obj", PARITY_MATRIX)
def test_fused_matches_staged_on_interpret_pallas_wave(name, n, f, leaves,
                                                       mode, obj):
    """The fused program inlines the learner's own grow closure, so the
    Pallas wave kernels (run in interpret mode on CPU) must produce the
    same trees through either entry granularity."""
    k = 7 if obj == "multiclass" else 2
    X, y = _xy(n, f, 7, classification=obj == "binary", num_class=k)
    p = {"objective": obj, "num_leaves": leaves, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_growth": "wave", "tpu_histogram_mode": mode,
         "tpu_pallas_interpret": True}
    if k > 2:
        p["num_class"] = k
    bf, bs = _pair(p, X, y, rounds=3)
    _assert_identical(bf, bs, X)


# ------------------------------------------------------------- eligibility

def _train_one(extra, n=300, f=6, **data_kw):
    X, y = _xy(n, f, 11, **data_kw)
    p = dict({"objective": "binary", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 5}, **extra)
    return lgb.train(p, lgb.Dataset(X, label=y, params=p),
                     num_boost_round=2)


def test_fused_supported_rejects_special_modes():
    cases = [
        ({"boosting_type": "dart"}, "dart"),
        ({"boosting_type": "goss"}, "goss"),
        ({"obs_health": "warn"}, "health"),
    ]
    for extra, tag in cases:
        bst = _train_one(extra)
        ok, why = fused_supported(bst._gbdt)
        assert not ok and why, tag

    X, y = _xy(300, 6, 11)
    p = {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
         "verbose": -1, "min_data_in_leaf": 5}
    bst = lgb.train(p, lgb.Dataset(X, label=(y + (X[:, 1] > 0)),
                                   params=p), num_boost_round=2)
    ok, why = fused_supported(bst._gbdt)
    assert ok and not why, "multiclass takes the fused class step"

    def fobj(preds, ds):
        g = preds - ds.get_label()
        return g, np.ones_like(g)

    p = {"objective": "none", "num_leaves": 7, "verbose": -1,
         "min_data_in_leaf": 5}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=2, fobj=fobj)
    ok, why = fused_supported(bst._gbdt)
    assert not ok and "fobj" in why


def test_fused_on_with_ineligible_config_stays_staged():
    """`on` must degrade to the staged chain (resolved once, cached as
    (None,)) instead of crashing when the config cannot fuse."""
    bst = _train_one({"boosting_type": "dart", "tpu_fused_iter": "on"})
    assert bst._gbdt._fused_state == (None,)


def test_fused_auto_stays_staged_on_plain_cpu():
    """auto only fuses where the wave Pallas kernels are active
    (ops/plan.py Plan.fused_wanted) — a default CPU run is not."""
    bst = _train_one({})
    assert bst._gbdt._fused_state == (None,)


def test_fused_iter_mode_validated():
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError, match="tpu_fused_iter"):
        _train_one({"tpu_fused_iter": "sometimes"})


# -------------------------------------------------------- zero host syncs

def test_default_boosting_loop_is_fence_free():
    """The complete-audit contract: every deliberate host sync in the
    training stack routes through obs/timers.fence, and a default run
    (NULL observer) must never hit it mid-tree.  Iteration 0 is burned
    outside the window — the periodic stop-check device_get fires every
    16 iterations starting there."""
    X, y = _xy(400, 6, 3)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbose": -1},
                      train_set=lgb.Dataset(X, label=y))
    bst.update()
    before = obs_timers.fence_count()
    for _ in range(3):
        bst.update()
    assert obs_timers.fence_count() == before


# --------------------------------------------------- band-probe regression

def test_band_probe_reproduces_and_fixes_the_degeneracy():
    """The minimal reproduction of the former 18-30 MB band: epsilon's
    W16 cell oversubscribes the Mosaic overlap window under the legacy
    row-tile plan and fits under the accumulator-aware one, while bosch
    W64 (chunked-RMW regime) never needed fixing."""
    from lightgbm_tpu.ops.pallas_wave import tile_plan_vmem_report
    rep = tile_plan_vmem_report(1 << 20, 2000, 64, 16)
    assert rep["pathological_old"] and not rep["pathological_new"]
    assert rep["live_new"] <= rep["overlap_window"] < rep["live_old"]
    chunked = tile_plan_vmem_report(1 << 20, 968, 64, 64)
    assert chunked["chunked_rmw"] and not chunked["pathological_old"]


# ------------------------------------------- dataset arrays are arguments

def test_fused_step_lowered_for_tpu_holds_no_dataset_literal(monkeypatch):
    """Every dataset-sized array (X, Xt, labels, weights) is an ARGUMENT
    of the fused program.  Lowered for tpu at 20,000 x 28 the module is
    under 1 MB of text; with Xt and the labels baked in as literals it
    was 1.6 MB here and grew with the row count (13.1 MB at 200,000)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.wave import make_wave_core, make_wave_jit

    n, f = 20_000, 28
    X, y = _xy(n, f, 3)
    w = np.random.default_rng(4).uniform(0.5, 2.0, n).astype(np.float32)
    p = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
         "min_data_in_leaf": 1, "verbose": -1}
    make_wave_core.cache_clear(); make_wave_jit.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        bst = lgb.Booster(params=p, train_set=lgb.Dataset(
            X, label=y, weight=w, params=p))
        gbdt = bst._gbdt
        fused = gbdt._resolve_fused_iter()
        assert fused is not None and gbdt.learner.hist_mode == "pallas_ct"
        assert gbdt.learner._Xt is not None
        args = fused.step_args(gbdt._score_dev[0], gbdt.learner._ones,
                               gbdt.learner._full_mask,
                               jnp.asarray(0.1, gbdt.score_dtype))
        # X, Xt, sign, label_weight, label, weights, score, row_mult
        big = [a for a in jax.tree_util.tree_leaves(args) if a.size >= n]
        assert len(big) == 8
        text = fused._step.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        monkeypatch.undo()
        make_wave_core.cache_clear(); make_wave_jit.cache_clear()
    assert len(text) < 1_000_000, len(text)


def test_fused_matches_staged_with_nested_objective_state():
    """lambdarank keeps its per-query tables as a list of dicts of device
    arrays next to plain ints; they all ride into the fused program as
    arguments and the trees stay those of the staged chain."""
    rng = np.random.default_rng(5)
    n, f = 300, 6
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + 2), 0, 4).astype(np.float32)
    group = np.full(n // 20, 20)
    p = {"objective": "lambdarank", "num_leaves": 7, "verbose": -1,
         "min_data_in_leaf": 5}
    boosters = []
    for mode in ("on", "off"):
        pm = dict(p, tpu_fused_iter=mode)
        boosters.append(lgb.train(
            pm, lgb.Dataset(X, label=y, group=group, params=pm),
            num_boost_round=2))
    _assert_identical(*boosters, X)


def test_fused_build_hides_no_trace_error(monkeypatch):
    """A built-in objective that fails to trace raises out of the fused
    build; only a user-defined objective running host code on the score
    sends the booster back to the staged chain."""
    import jax
    from lightgbm_tpu import objectives
    from lightgbm_tpu.ops.fused_iter import FusedIteration

    def host_gradients(self, score):
        return np.asarray(score) - 1.0, np.ones(len(score))

    class HostObjective(objectives.RegressionL2loss):
        get_gradients = host_gradients

    X, y = _xy(300, 6, 6, classification=False)
    p = {"objective": "regression", "num_leaves": 7, "verbose": -1}
    gbdt = lgb.Booster(params=p,
                       train_set=lgb.Dataset(X, label=y, params=p))._gbdt
    host = HostObjective()
    host.init(gbdt.train_data.metadata, gbdt.num_data)
    assert FusedIteration.build(gbdt.learner, host, gbdt.num_data,
                                gbdt.score_dtype) is None
    monkeypatch.setattr(objectives.RegressionL2loss, "get_gradients",
                        host_gradients)
    with pytest.raises(jax.errors.TracerArrayConversionError):
        FusedIteration.build(gbdt.learner, gbdt.objective, gbdt.num_data,
                             gbdt.score_dtype)
