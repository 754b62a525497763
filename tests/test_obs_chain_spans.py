"""The staged chain's host steps as spans of the ring (PR 37): `gradients`,
`grow_args`, `dispatch` and `score_update` under one `iteration`; the fused
step keeps its two; ``host_orchestration_s`` is the iteration's own host
time read from the ring (obs/timers.py open_self_seconds), not a second
clock.  The ring is process-global: every test clears it first."""
import json
import time

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import timers

CHAIN = ["gradients", "grow_args", "dispatch", "score_update"]
WAVE = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
        "verbose": -1, "tpu_growth": "wave", "tpu_histogram_mode": "pallas_t",
        "tpu_pallas_interpret": True, "tpu_wave_width": 4}


@pytest.fixture(autouse=True)
def _empty_ring():
    timers.clear()
    yield
    timers.clear()


def _xy(n=400, f=6, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.1 * rng.standard_normal(n) > 0)
    return X, y.astype(np.float32)


def _one_update(params):
    """The span records of one warm update() of a fresh booster."""
    X, y = _xy()
    bst = lgb.Booster(dict(params), lgb.Dataset(X, label=y,
                                                params=dict(params)))
    bst.update()                        # compiles
    timers.clear()
    bst.update()
    return [r for r in timers.snapshot() if r["kind"] == "span"]


def _children(spans):
    (it,) = [r for r in spans if r["name"] == "iteration"]
    return it, [r for r in spans if r["cause"] == it["seq"]]


@pytest.fixture
def four_devices(monkeypatch):
    from lightgbm_tpu.parallel import mesh as mesh_mod
    make = mesh_mod.make_data_mesh
    monkeypatch.setattr(mesh_mod, "make_data_mesh",
                        lambda devices=None: make(jax.devices()[:4]))


def test_data_parallel_update_spans_its_host_steps_under_one_iteration(
        four_devices):
    spans = _one_update({"objective": "binary", "num_leaves": 7,
                         "verbose": -1, "tree_learner": "data"})
    it, kids = _children(spans)
    assert [r["name"] for r in kids] == CHAIN
    assert len(spans) == len(CHAIN) + 1
    for a, b in zip(kids, kids[1:]):        # one after the other, in order
        assert a["t1"] <= b["t0"]
    for r in kids:
        assert it["t0"] <= r["t0"] and r["t1"] <= it["t1"]
        assert r["ids"] == it["ids"]


@pytest.mark.parametrize("fused, names", [
    ("on", ["dispatch", "iteration"]),
    ("off", CHAIN + ["iteration"])])
def test_fused_step_keeps_its_two_spans_and_the_staged_chain_has_five(
        fused, names):
    spans = _one_update(dict(WAVE, tpu_fused_iter=fused))
    assert [r["name"] for r in spans] == names


def test_validation_scores_update_under_a_span_of_their_own():
    X, y = _xy()
    params = dict(WAVE, tpu_fused_iter="on")
    train = lgb.Dataset(X, label=y, params=params)
    valid = lgb.Dataset(X[:100], label=y[:100], reference=train)
    lgb.train(params, train, num_boost_round=2, valid_sets=[valid])
    its = [r for r in timers.snapshot() if r["name"] == "iteration"]
    assert len(its) == 2
    for it in its:
        kids = [r["name"] for r in timers.snapshot()
                if r["kind"] == "span" and r["cause"] == it["seq"]
                and r["name"] not in ("trace", "lower", "compile",
                                      "cache_load")]
        assert kids == ["dispatch", "score_update"]


def test_open_self_seconds_leaves_out_children_and_own_fences(monkeypatch):
    assert timers.open_self_seconds() == 0.0          # no span open
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda value: time.sleep(0.3))
    with timers.span("iteration", it=0) as rec:
        with timers.span("dispatch"):
            time.sleep(0.3)
        timers.fence(np.zeros(3))                     # waits 0.3 s
        own = timers.open_self_seconds()
        elapsed = (time.perf_counter_ns() - rec["t0"]) * 1e-9
    assert rec["fenced_ns"] >= 0.3e9
    assert elapsed >= 0.6
    assert 0.0 <= own < 0.15
    # the ring's own reading of the closed span keeps the fence in it
    (closed,) = [r for r in timers.snapshot() if r["name"] == "iteration"]
    assert timers.self_seconds(timers.snapshot())[closed["seq"]] >= 0.3


@pytest.mark.parametrize("fused", ["on", "off"])
def test_host_orchestration_is_read_from_the_ring(fused, tmp_path):
    """With the observer on, every `iter` event carries the iteration's
    host time outside its calls and fences: at least 0, at most the
    iteration's wall time; the second clock is gone."""
    assert not hasattr(timers, "OrchestrationClock")
    X, y = _xy()
    path = str(tmp_path / "events.jsonl")
    params = dict(WAVE, tpu_fused_iter=fused, obs_events_path=path,
                  obs_timing="phase")
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=3)
    bst.finalize_telemetry()
    with open(path) as f:
        iters = [e for e in map(json.loads, filter(str.strip, f))
                 if e.get("ev") == "iter"]
    assert len(iters) == 3
    for e in iters:
        assert 0.0 <= e["host_orchestration_s"] <= e["time_s"]
