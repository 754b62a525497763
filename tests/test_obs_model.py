"""Model & data observability (obs/model.py, obs/dataquality.py).

Covers the split audit trail (parity vs the dumped tree structure),
importance evolution (events + Booster.importance_history round-trip),
prediction attribution (pred_contrib sums to the raw score), data-quality
profiling (degeneracy flags, the obs_health=fatal abort), the ``obs
explain`` report, the single-bucket metrics counter, and the
final_eval_metric gate in tools/bench_compare.py.
"""
import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import read_events
from lightgbm_tpu.obs.dataquality import build_findings, label_profile
from lightgbm_tpu.obs.metrics import REGISTRY
from lightgbm_tpu.obs.model import audit_margin_stats, importance_history

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _data(n=400, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    y = (X @ w + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    return X, y


def _train(params, path, n_rounds=5, X=None, y=None, valid=False):
    if X is None:
        X, y = _data()
    ds = lgb.Dataset(X, label=y)
    base = {"objective": "binary", "num_leaves": 7, "verbose": -1,
            "obs_events_path": str(path)}
    base.update(params)
    kw = {}
    if valid:
        Xv, yv = _data(seed=1)
        kw["valid_sets"] = [lgb.Dataset(Xv, label=yv, reference=ds)]
    return lgb.train(base, ds, num_boost_round=n_rounds, **kw)


# ------------------------------------------------------- split audit trail

def test_split_audit_matches_tree_dump(tmp_path):
    path = tmp_path / "ev.jsonl"
    bst = _train({"obs_split_audit": True}, path, n_rounds=4)
    audits = [e for e in read_events(path) if e["ev"] == "split_audit"]
    assert audits, "no split_audit events"
    assert [e["tree"] for e in audits] == list(range(len(audits)))
    dump = bst.dump_model()
    for e in audits:
        # index the dumped tree's internal nodes by split_index
        nodes = {}

        def walk(node):
            if "split_index" in node:
                nodes[node["split_index"]] = node
                walk(node["left_child"])
                walk(node["right_child"])

        walk(dump["tree_info"][e["tree"]]["tree_structure"])
        assert e["splits"], "audited tree with no splits"
        assert e["num_leaves"] == len(e["splits"]) + 1
        for s in e["splits"]:
            node = nodes[s["node"]]
            assert s["feature"] == node["split_feature"]
            assert s["gain"] == pytest.approx(node["split_gain"], rel=1e-6)
            assert s["count"] == node["internal_count"]
            assert s["left_count"] + s["right_count"] == s["count"]
            assert s["gain"] > 0
            if "second_feature" in s:
                # the runner-up lost: its gain can't beat the winner's
                assert s["second_gain"] <= s["gain"] + 1e-6
                assert s["margin"] == pytest.approx(
                    s["gain"] - s["second_gain"], abs=1e-9)
                assert s["second_feature"] != s["feature"]


def test_audit_margin_stats_aggregates(tmp_path):
    path = tmp_path / "ev.jsonl"
    _train({"obs_split_audit": True}, path, n_rounds=4)
    events = read_events(path)
    stats = audit_margin_stats(events)
    assert stats
    n_splits = sum(len(e["splits"]) for e in events
                   if e["ev"] == "split_audit")
    assert sum(st["splits"] for st in stats.values()) == n_splits
    for st in stats.values():
        assert st["contested"] <= st["splits"]
        assert st["total_gain"] > 0
        if st["median_margin_rel"] is not None:
            assert 0.0 <= st["median_margin_rel"]


# ---------------------------------------------------- importance evolution

def test_importance_events_and_history_round_trip(tmp_path):
    path = tmp_path / "ev.jsonl"
    bst = _train({"obs_importance_every": 2}, path, n_rounds=5)
    events = read_events(path)
    imps = [e for e in events if e["ev"] == "importance"]
    assert [e["it"] for e in imps] == [0, 2, 4]
    # the final snapshot must agree with the end-of-training importances
    hist = importance_history(events, "split")
    assert [h["it"] for h in hist] == [0, 2, 4]
    dense = bst.feature_importance("split")
    for f, v in hist[-1]["importance"].items():
        assert v == dense[f]
    gains = bst.feature_importance("gain")
    for f, v in importance_history(events, "gain")[-1]["importance"].items():
        assert v == pytest.approx(gains[f], rel=1e-6)
    # Booster.importance_history reads its own telemetry
    assert bst.importance_history("split") == hist
    with pytest.raises(ValueError):
        importance_history(events, "cover")
    # trajectories only grow: split counts are cumulative
    for f in hist[-1]["importance"]:
        series = [h["importance"].get(f, 0.0) for h in hist]
        assert series == sorted(series)


# -------------------------------------------------- prediction attribution

def test_pred_contrib_sums_to_raw(tmp_path):
    X, y = _data()
    bst = _train({}, tmp_path / "ev.jsonl", n_rounds=5, X=X, y=y)
    raw = bst.predict(X, raw_score=True)
    contrib = bst.predict(X, pred_contrib=True)
    assert contrib.shape == (len(X), X.shape[1] + 1)
    np.testing.assert_allclose(contrib.sum(axis=1), raw, atol=1e-5)
    # per-tree attribution sums to the same raw score
    per_tree = bst._gbdt.pred_contrib(X, per="tree")
    assert per_tree.shape[0] == len(X)
    np.testing.assert_allclose(per_tree.sum(axis=1), raw, atol=1e-5)
    with pytest.raises(KeyError):
        bst._gbdt.pred_contrib(X, per="leaf")


def test_pred_contrib_respects_num_iteration(tmp_path):
    X, y = _data()
    bst = _train({}, tmp_path / "ev.jsonl", n_rounds=4, X=X, y=y)
    raw2 = bst.predict(X, raw_score=True, num_iteration=2)
    contrib2 = bst.predict(X, pred_contrib=True, num_iteration=2)
    np.testing.assert_allclose(contrib2.sum(axis=1), raw2, atol=1e-5)
    per_tree2 = bst._gbdt.pred_contrib(X, num_iteration=2, per="tree")
    assert per_tree2.shape[1] == 2


# ----------------------------------------------------- data-quality profile

def test_data_profile_flags_constant_and_imbalance(tmp_path):
    path = tmp_path / "ev.jsonl"
    rng = np.random.default_rng(3)
    X = rng.normal(size=(300, 5))
    X[:, 3] = 0.0                          # constant (single-bucket)
    y = np.zeros(300)
    y[:2] = 1.0                            # minority fraction 1/150
    _train({}, path, n_rounds=2, X=X, y=y)
    profiles = [e for e in read_events(path) if e["ev"] == "data_profile"]
    assert len(profiles) == 1
    p = profiles[0]
    assert p["n_features"] == 5
    assert 3 in p["constant"]
    assert p["label"]["n_distinct"] == 2
    assert p["label"]["min_class_frac"] == pytest.approx(2 / 300, abs=1e-6)
    flags = {f["flag"]: f["severity"] for f in p["findings"]}
    assert flags.get("constant") == "error"
    assert flags.get("label_imbalance") == "warning"
    # per-feature arrays present for small F
    assert p["missing_rate"][3] == 0.0
    assert p["entropy"][3] is None


def test_constant_nonzero_feature_fatal_abort(tmp_path):
    path = tmp_path / "ev.jsonl"
    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 5))
    X[:, 2] = 3.14     # constant NONZERO: still bins into two buckets,
    y = (X[:, 0] > 0).astype(np.float64)   # only one of them occupied
    with pytest.raises(lgb.LightGBMError, match="feature 2"):
        _train({"obs_health": "fatal"}, path, n_rounds=3, X=X, y=y)
    events = [json.loads(ln) for ln in open(path)]
    health = [e for e in events if e.get("ev") == "health"
              and e.get("check") == "data_profile"]
    assert [(h["status"], h["detail"]["feature"], h["detail"]["flag"])
            for h in health] == [("fatal", 2, "constant")]
    # the flight record survives the abort
    assert os.path.exists(str(path) + ".flight.json")
    # warn mode must train through the same data
    bst = _train({"obs_health": "warn"}, tmp_path / "warn.jsonl",
                 n_rounds=3, X=X, y=y)
    assert bst.num_trees() == 3


def test_data_profile_opt_out(tmp_path):
    path = tmp_path / "ev.jsonl"
    _train({"obs_data_profile": False}, path, n_rounds=2)
    assert not [e for e in read_events(path) if e["ev"] == "data_profile"]


def test_label_profile_and_findings_unit():
    lp = label_profile(np.zeros(10))
    assert lp["n_distinct"] == 1
    findings = build_findings({"n_features": 0}, lp)
    assert [f["flag"] for f in findings] == ["single_class_label"]
    assert findings[0]["severity"] == "error"
    assert label_profile(None) == {"n": 0}
    # a regression-shaped label: distinct count only, no class table
    lp = label_profile(np.linspace(0.0, 1.0, 100))
    assert lp["n_distinct"] == 100 and "classes" not in lp


def test_single_bucket_counter_with_obs_off():
    counter = REGISTRY.counter("dataset_single_bucket_features_total")
    before = counter.value
    X, y = _data(n=200, f=4)
    X[:, 1] = 0.0
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    assert counter.value >= before + 1
    profile = ds._handle._data_profile
    assert profile is None or 1 in profile["constant"]


# ------------------------------------------------------------- obs explain

def test_obs_explain_report(tmp_path, capsys):
    from lightgbm_tpu.obs import query
    path = tmp_path / "ev.jsonl"
    _train({"obs_split_audit": True, "obs_importance_every": 2,
            "metric": "auc"}, path, n_rounds=5, valid=True)
    rc = query.main(["explain", str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "data profile (train)" in out
    assert "no data-quality findings" in out
    assert "features by final gain" in out
    assert "split-audit gain margins" in out
    assert "convergence (eval events):" in out
    assert "valid_0 auc" in out
    # --check passes on a clean run, fails on an error-severity finding
    assert query.main(["explain", str(path), "--check"]) == 0
    bad = tmp_path / "bad.jsonl"
    X, y = _data(n=200, f=4)
    X[:, 0] = 0.0
    try:
        _train({"obs_health": "fatal"}, bad, n_rounds=2, X=X, y=y)
    except lgb.LightGBMError:
        pass
    capsys.readouterr()
    assert query.main(["explain", str(bad), "--check"]) == 1
    assert "[error]" in capsys.readouterr().out


def test_obs_explain_cli_subprocess(tmp_path):
    path = tmp_path / "ev.jsonl"
    _train({"obs_split_audit": True, "obs_importance_every": 2}, path,
           n_rounds=3)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", "lightgbm_tpu", "obs",
                        "explain", str(path)], capture_output=True,
                       text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "split-audit gain margins" in r.stdout


def test_obs_explain_empty_timeline(tmp_path):
    from lightgbm_tpu.obs import query
    path = tmp_path / "ev.jsonl"
    _train({"obs_data_profile": False}, path, n_rounds=2)
    buf = io.StringIO()
    from lightgbm_tpu.obs.query import last_run, load_timeline
    assert query.render_explain(last_run(load_timeline(str(path))),
                                out=buf) is False
    # a model/data-quiet run has nothing to explain (the learner queues
    # no construction-time event any more): the fallback says how to
    # populate the report
    assert "no model/data events" in buf.getvalue()
    # and so does a timeline with no events at all
    buf2 = io.StringIO()
    assert query.render_explain([], out=buf2) is False
    assert "no model/data events" in buf2.getvalue()


# ---------------------------------------------------------------- plotting

def test_plot_importance_history_sources(tmp_path):
    pytest.importorskip("matplotlib")
    import matplotlib
    matplotlib.use("Agg")
    from lightgbm_tpu.plotting import (plot_importance,
                                       plot_importance_history)
    path = tmp_path / "ev.jsonl"
    bst = _train({"obs_importance_every": 2}, path, n_rounds=5)
    # timeline path, Booster, and history-result sources all plot
    ax = plot_importance(str(path), importance_type="gain")
    assert ax.get_title() == "Feature importance"
    ax = plot_importance_history(str(path))
    assert len(ax.get_lines()) > 0
    ax = plot_importance_history(bst)
    assert len(ax.get_lines()) > 0
    ax = plot_importance_history(bst.importance_history("gain"))
    assert len(ax.get_lines()) > 0
    with pytest.raises(ValueError):
        plot_importance_history([])


# ------------------------------------------- bench_compare eval-metric gate

def _eval_timeline(path, value):
    with open(path, "w") as f:
        f.write(json.dumps({"ev": "eval", "run": "r", "t": 0.0, "it": 0,
                            "results": [{"dataset": "valid_1",
                                         "metric": "auc",
                                         "value": value}]}) + "\n")


def test_bench_compare_gates_on_eval_metric(tmp_path):
    base, cand = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    _eval_timeline(base, 0.90)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmp_py = os.path.join(REPO, "tools", "bench_compare.py")
    # within tolerance (default 2%): 0.89 vs 0.90 passes
    _eval_timeline(cand, 0.89)
    r = subprocess.run([sys.executable, cmp_py, base, cand],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "final_eval_metric" in r.stdout
    # beyond tolerance: 0.80 vs 0.90 is a quality regression
    _eval_timeline(cand, 0.80)
    r = subprocess.run([sys.executable, cmp_py, base, cand],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 1
    assert "REGRESSED" in r.stdout
