"""Multiclass on the fused iteration (ops/fused_iter.py): one dispatch an
iteration computes the softmax over the (k, N) score once and grows the
trained classes' trees with ONE grow body, a `lax.scan` over the classes.

* the fused iteration against the staged chain (``tpu_fused_iter=off``):
  the same trees, model text and scores to the bit, k = 3 and 7, on the
  default grower and on the Pallas wave path through the interpreter;
* a class that ``class_need_train`` skips drops out of the scan by a
  static index list, and the stub tree stays the staged chain's;
* the binary step traces the program it always did (a pinned digest);
* the counter ``class_trees`` and the span ``class_unstack``.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import timers

WAVE = {"tpu_growth": "wave", "tpu_histogram_mode": "pallas_ct",
        "tpu_pallas_interpret": True}


def _binary_xy(n=600, f=10, seed=7):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


# ------------------------------------------------- the binary step stands

# sha256 of the jaxpr text of the binary fused step (600 x 10, 15 leaves,
# `_binary_xy`) as the one-class program traced it before the multiclass
# step existed (jax 0.9.0): on the default grower, and on the wave grower
# with the pallas_ct kernel through the interpreter
BINARY_STEP_JAXPRS = {
    "exact": "b18c2070c441cbb52583bf167ac0a2c4"
             "c87976e51e8508fdc6811f324fc1987d",
    "pallas_ct": "443ec793ee926e3e1cbc135799bf7a89"
                 "20a5dda99ab016eb4cc186bf610a971b",
}


@pytest.mark.parametrize("growth", list(BINARY_STEP_JAXPRS))
def test_the_binary_step_traces_the_program_it_did(growth):
    X, y = _binary_xy()
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tpu_fused_iter": "on",
         **(WAVE if growth == "pallas_ct" else {})}
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    gbdt = bst._gbdt
    fused = gbdt._resolve_fused_iter()
    args = fused.step_args(gbdt._score_dev[0], gbdt.learner._ones,
                           gbdt.learner._full_mask,
                           jnp.asarray(0.1, gbdt.score_dtype))
    text = str(jax.make_jaxpr(fused._step)(*args))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        BINARY_STEP_JAXPRS[growth]


# ------------------------------------------- fused against the staged chain

def _classes_xy(n, f, k, seed, skip=()):
    """Labels from the argmax of k noisy scores; `skip`'s classes get no
    row (their rows go to the next class)."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    score = X[:, :k] + 0.4 * rng.standard_normal((n, k))
    score[:, list(skip)] = -np.inf
    return X, np.argmax(score, axis=1).astype(np.float32)


def _train_pair(params, X, y, rounds=3):
    out = []
    for mode in ("on", "off"):
        p = dict(params, tpu_fused_iter=mode)
        out.append(lgb.train(p, lgb.Dataset(X, label=y, params=p),
                             num_boost_round=rounds))
    return out


def _assert_same(fused, staged, X):
    assert fused._gbdt._fused_state[0] is not None
    assert fused._gbdt._fused_state[0].classes is not None
    assert staged._gbdt._fused_state == (None,)
    assert fused.model_to_string() == staged.model_to_string()
    np.testing.assert_array_equal(np.asarray(fused._gbdt._score_dev),
                                  np.asarray(staged._gbdt._score_dev))
    np.testing.assert_array_equal(fused.predict(X), staged.predict(X))


CASES = [
    ("softmax-3-default", "multiclass", 3, {}),
    ("softmax-7-pallas_ct", "multiclass", 7, WAVE),
    ("softmax-3-pallas_t", "multiclass", 3,
     dict(WAVE, tpu_histogram_mode="pallas_t")),
    ("ova-3-default", "multiclassova", 3, {}),
]


@pytest.mark.parametrize("name,objective,k,extra", CASES,
                         ids=[c[0] for c in CASES])
def test_the_class_step_grows_the_staged_chains_trees(name, objective, k,
                                                      extra):
    X, y = _classes_xy(2400, 12, k, seed=k)
    p = {"objective": objective, "num_class": k, "num_leaves": 15,
         "min_data_in_leaf": 5, "verbose": -1, **extra}
    fused, staged = _train_pair(p, X, y)
    _assert_same(fused, staged, X)
    assert len(fused._gbdt.models) == 3 * k


def test_a_class_with_no_row_drops_out_of_the_scan():
    """class_need_train skips class 1 (no row has it): the scan grows
    classes 0 and 2 from the static list, the stub tree and its default
    output are the staged chain's."""
    X, y = _classes_xy(2400, 12, 3, seed=5, skip=(1,))
    assert not (y == 1).any()
    p = {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
         "min_data_in_leaf": 5, "verbose": -1}
    fused, staged = _train_pair(p, X, y)
    assert fused._gbdt.class_need_train == [True, False, True]
    assert fused._gbdt._fused_state[0].classes == (0, 2)
    _assert_same(fused, staged, X)
    # the first iteration's stub carries the class's default output
    stubs = fused._gbdt.models[1::3]
    assert [t.num_leaves for t in stubs] == [2, 1, 1]
    assert stubs[0].leaf_value[0] == fused._gbdt.class_default_output[1]


def test_the_class_step_is_one_dispatch_and_records_what_it_grew():
    """One `dispatch` span an iteration, the counter `class_trees` where
    the step is built, one `tree` record a class tree with its grow
    loop's counters, and `class_unstack` where the batch is split."""
    X, y = _classes_xy(2000, 10, 4, seed=9, skip=(3,))
    p = {"objective": "multiclass", "num_class": 4, "num_leaves": 7,
         "min_data_in_leaf": 5, "verbose": -1, "tpu_fused_iter": "on",
         **WAVE}
    timers.clear()
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    for _ in range(2):
        bst.update()
    bst._gbdt._materialize()
    recs = timers.snapshot()
    built = [r["fields"] for r in recs if r["name"] == "class_trees"]
    assert built == [{"classes": 4, "trained": 3}]
    iterations = [r["seq"] for r in recs if r["name"] == "iteration"]
    dispatches = [r for r in recs if r["name"] == "dispatch"]
    assert len(iterations) == 2 and len(dispatches) == 2
    assert {r["cause"] for r in dispatches} == set(iterations)
    trees = [r["fields"] for r in recs if r["name"] == "tree"]
    assert [(t["it"], t["tree"]) for t in trees] == \
        [(i, c) for i in range(2) for c in range(3)]
    assert all(t["committed"] >= 1 and t["waves"] >= 1 for t in trees)
    unstack = [r for r in recs if r["name"] == "class_unstack"]
    assert [r["ids"]["trees"] for r in unstack] == [6]


def test_a_validation_set_scores_the_class_trees_as_the_staged_chain():
    """Where a validation set is attached its scores follow each class
    tree, sliced from the step's batch on the device."""
    X, y = _classes_xy(2400, 12, 3, seed=13)
    p = {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
         "min_data_in_leaf": 5, "verbose": -1, "metric": "multi_logloss"}
    out = []
    for mode in ("on", "off"):
        pm = dict(p, tpu_fused_iter=mode)
        train = lgb.Dataset(X[:1800], label=y[:1800], params=pm)
        valid = lgb.Dataset(X[1800:], label=y[1800:], reference=train,
                            params=pm)
        bst = lgb.train(pm, train, num_boost_round=3, valid_sets=[valid])
        out.append((bst, bst.eval_valid()))
    (fused, fused_eval), (staged, staged_eval) = out
    assert fused._gbdt._fused_state[0].classes == (0, 1, 2)
    assert fused.model_to_string() == staged.model_to_string()
    assert fused_eval == staged_eval
    np.testing.assert_array_equal(
        np.asarray(fused._gbdt._valid_score_dev[0]),
        np.asarray(staged._gbdt._valid_score_dev[0]))
