"""Run telemetry (lightgbm_tpu/obs): schema, timers, wiring, overhead.

Covers the observability subsystem end-to-end on the CPU backend:
JSONL schema validation of an emitted timeline, the compile-vs-execute
split, fencing semantics, callback/timeline integration, config/CLI
round-trips, profiler-window logic (monkeypatched tracer), Log
redirection, the trace_summary JSONL reader, bench --dry, and the
disabled-path overhead guard.
"""
import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import (NULL_OBSERVER, SCHEMA_VERSION, RunObserver,
                              observer_from_config, read_events,
                              validate_event)
from lightgbm_tpu.utils.config import Config
from lightgbm_tpu.utils.log import Log

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _data(n=400, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    y = (X @ w + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    return X, y


def _train(params, path, n_rounds=5, valid=False, callbacks=None):
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    base = {"objective": "binary", "num_leaves": 7, "verbose": -1,
            "obs_events_path": str(path)}
    base.update(params)
    kw = {}
    if valid:
        Xv, yv = _data(seed=1)
        kw["valid_sets"] = [lgb.Dataset(Xv, label=yv, reference=ds)]
    return lgb.train(base, ds, num_boost_round=n_rounds,
                     callbacks=callbacks, **kw)


# ---------------------------------------------------------------- schema

def test_emitted_timeline_is_schema_valid(tmp_path):
    path = tmp_path / "ev.jsonl"
    _train({"obs_memory_every": 2}, path)
    events = read_events(path)            # validates every record
    kinds = [e["ev"] for e in events]
    assert kinds[0] == "run_header"
    assert kinds[-1] == "run_end"
    for need in ("iter", "compile", "memory"):
        assert need in kinds
    header = events[0]
    assert header["schema"] == SCHEMA_VERSION
    assert header["backend"] == "cpu"
    assert len(header["devices"]) == 8    # conftest's virtual mesh
    assert header["context"]["learner"]
    # every record of one run shares the run id
    assert len({e["run"] for e in events}) == 1


def test_validate_event_rejects_bad_records():
    # unknown event types pass by default (forward compatibility: older
    # readers must accept newer-schema timelines) but fail under strict
    unknown = {"ev": "nope", "t": 0, "run": "x"}
    assert validate_event(unknown) is unknown
    with pytest.raises(ValueError):
        validate_event(unknown, strict=True)
    with pytest.raises(ValueError):
        validate_event({"ev": "iter", "t": 0, "run": "x"})   # missing keys
    with pytest.raises(ValueError):
        validate_event({"ev": "run_header", "t": 0, "run": "x",
                        "schema": 99, "backend": "cpu", "devices": [],
                        "params": {}, "context": {}, "timing": "phase"})
    validate_event({"ev": "iter", "t": 0, "run": "x", "it": 0,
                    "time_s": 0.1, "phases": {}, "fenced": True})


def test_iter_records_carry_phases_and_fencing(tmp_path):
    path = tmp_path / "ev.jsonl"
    _train({"obs_timing": "phase"}, path, valid=True)
    iters = [e for e in read_events(path) if e["ev"] == "iter"]
    assert len(iters) == 5
    assert [e["it"] for e in iters] == list(range(5))
    for e in iters:
        assert e["fenced"] is True
        assert e["time_s"] > 0
        for phase in ("boost", "grow", "partition", "update"):
            assert phase in e["phases"], e["phases"]
        # phase laps can never exceed the fenced iteration total
        assert sum(e["phases"].values()) <= e["time_s"] + 1e-6


def test_timing_off_never_fences(tmp_path):
    path = tmp_path / "ev.jsonl"
    _train({"obs_timing": "off"}, path)
    events = read_events(path)
    for e in events:
        if e["ev"] in ("iter", "compile"):
            assert e["fenced"] is False


# --------------------------------------------- compile vs execute split

def test_compile_execute_split(tmp_path):
    path = tmp_path / "ev.jsonl"
    _train({"obs_timing": "phase"}, path)
    events = read_events(path)
    compiles = [e for e in events if e["ev"] == "compile"]
    assert [e["entry"] for e in compiles] == ["tree_grow"]
    run_end = events[-1]
    st = run_end["entries"]["tree_grow"]
    assert run_end["iters"] == 5
    # first call compiled; the 4 later calls are steady-state executes
    # (jit caches may be warm from earlier tests in this module, so only
    # the split's bookkeeping — not first_s >> exec — can be asserted)
    assert st["exec_n"] == 4
    assert st["first_s"] > 0
    assert st["exec_max_s"] >= st["exec_min_s"] > 0
    assert st["compile_est_s"] >= 0
    assert run_end["phase_totals"]["grow"] > 0


def test_entry_timers_unit():
    from lightgbm_tpu.obs.timers import EntryTimers
    t = EntryTimers()
    assert t.record("e", 2.0) is True          # first call -> compile
    assert t.record("e", 0.5) is False
    assert t.record("e", 0.25) is False
    s = t.summary()["e"]
    assert s["exec_n"] == 2
    assert s["exec_min_s"] == 0.25 and s["exec_max_s"] == 0.5
    assert s["exec_mean_s"] == pytest.approx(0.375)
    assert s["compile_est_s"] == pytest.approx(2.0 - 0.375)


def test_fence_is_type_forgiving():
    import jax.numpy as jnp
    from lightgbm_tpu.obs.timers import fence
    fence(None)
    fence(3.5)
    fence(np.zeros(3))
    fence((jnp.ones(2), [jnp.zeros(1), None]))


# ------------------------------------------- callback / timeline access

def test_record_telemetry_and_booster_timeline(tmp_path):
    records = []
    bst = _train({}, tmp_path / "ev.jsonl",
                 callbacks=[lgb.record_telemetry(records)])
    tl = bst.telemetry()
    assert tl[-1]["ev"] == "run_end"
    # the callback saw everything up to finalization; finalize itself
    # appends only the profiler's final window flush (obs_prof_hz is
    # on by default) and run_end
    assert len(records) < len(tl)
    tail = {e["ev"] for e in tl[len(records):]}
    assert tail <= {"prof_profile", "metrics", "run_end"}, tail
    assert sum(1 for e in records if e["ev"] == "iter") == 5
    with pytest.raises(TypeError):
        lgb.record_telemetry({})


def test_telemetry_disabled_by_default():
    X, y = _data()
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbose": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=2)
    assert bst._gbdt._obs is NULL_OBSERVER
    assert bst.telemetry() == []


def test_cv_folds_share_file_distinct_runs(tmp_path):
    path = tmp_path / "cv.jsonl"
    X, y = _data()
    lgb.cv({"objective": "binary", "num_leaves": 7, "verbose": -1,
            "obs_events_path": str(path)}, lgb.Dataset(X, label=y),
           num_boost_round=3, nfold=2, stratified=False)
    events = read_events(path)
    runs = {e["run"] for e in events}
    assert len(runs) == 2                  # one run id per fold
    for run in runs:
        kinds = [e["ev"] for e in events if e["run"] == run]
        assert kinds.count("run_header") == 1
        assert kinds.count("run_end") == 1
        assert kinds.count("iter") == 3


# --------------------------------------------------- config round-trip

def test_config_aliases_round_trip():
    cfg = Config({"obs_events_file": "/tmp/x.jsonl",
                  "obs_profile_iters": "3:5",
                  "obs_profile_dir": "/tmp/tr",
                  "obs_memory_freq": 4})
    assert cfg.obs_events_path == "/tmp/x.jsonl"
    assert cfg.obs_trace_iters == "3:5"
    assert cfg.obs_trace_dir == "/tmp/tr"
    assert cfg.obs_memory_every == 4


def test_observer_from_config_policies():
    assert observer_from_config(Config({})) is NULL_OBSERVER
    obs = observer_from_config(Config({"obs_events_path": "/tmp/x.jsonl"}))
    assert isinstance(obs, RunObserver) and obs.timing == "phase"
    obs = observer_from_config(Config({"obs_events_path": "/tmp/x.jsonl",
                                       "obs_timing": "iter"}))
    assert obs.timing == "iter"
    with pytest.raises(lgb.LightGBMError):
        observer_from_config(Config({"obs_events_path": "/tmp/x.jsonl",
                                     "obs_timing": "sideways"}))
    with pytest.raises(lgb.LightGBMError):
        # trace window without a destination
        observer_from_config(Config({"obs_trace_iters": "1:2"}))


def test_cli_smoke_on_shipped_example(tmp_path, monkeypatch):
    """The shipped examples/binary_classification data + confs run as-is,
    and the CLI grows the obs flags (events path relative to cwd)."""
    import shutil
    from lightgbm_tpu import cli
    src = os.path.join(REPO, "examples", "binary_classification")
    work = tmp_path / "ex"
    shutil.copytree(src, work)
    monkeypatch.chdir(work)
    rc = cli.main(["config=train.conf", "num_trees=3", "metric_freq=1",
                   "obs_events_path=events.jsonl", "obs_timing=iter",
                   "obs_memory_every=2"])
    assert rc == 0
    assert (work / "LightGBM_model.txt").exists()
    events = read_events(work / "events.jsonl")
    kinds = [e["ev"] for e in events]
    assert kinds[0] == "run_header" and kinds[-1] == "run_end"
    assert kinds.count("iter") == 3
    rc = cli.main(["config=predict.conf"])
    assert rc == 0
    preds = (work / "LightGBM_predict_result.txt").read_text().split()
    assert len(preds) == 400               # binary.test rows


# ----------------------------------------------------- profiler window

def test_trace_window_opens_and_closes(monkeypatch, tmp_path):
    from lightgbm_tpu.obs import profile
    calls = []
    monkeypatch.setattr(profile, "_start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(profile, "_stop_trace",
                        lambda: calls.append(("stop",)))
    path = tmp_path / "ev.jsonl"
    _train({"obs_trace_iters": "1:3", "obs_trace_dir": str(tmp_path)},
           path)
    assert calls == [("start", str(tmp_path)), ("stop",)]
    windows = [e for e in read_events(path) if e["ev"] == "trace_window"]
    assert [(w["action"], w["it"]) for w in windows] == [("start", 1),
                                                         ("stop", 2)]


def test_trace_window_force_stop_on_short_run(monkeypatch, tmp_path):
    from lightgbm_tpu.obs import profile
    calls = []
    monkeypatch.setattr(profile, "_start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(profile, "_stop_trace",
                        lambda: calls.append("stop"))
    # window [1, 100) stays open at run end -> finalize must close it
    _train({"obs_trace_iters": "1:100", "obs_trace_dir": str(tmp_path)},
           tmp_path / "ev.jsonl", n_rounds=3)
    assert calls == ["start", "stop"]


def test_parse_trace_iters():
    from lightgbm_tpu.obs.profile import parse_trace_iters
    assert parse_trace_iters("") is None
    assert parse_trace_iters("3:8") == (3, 8)
    assert parse_trace_iters(" 0:1 ") == (0, 1)
    for bad in ("5", "5:5", "8:3", "-1:4", "a:b", "1:2:3"):
        with pytest.raises(lgb.LightGBMError):
            parse_trace_iters(bad)


# ------------------------------------------------------- log redirection

def test_log_set_stream_captures_output():
    # earlier trainings ran verbose=-1; pin the level for this test
    level = Log._level
    Log.reset_level(1)
    buf = io.StringIO()
    prev = Log.set_stream(buf)
    try:
        Log.warning("obs test %d", 7)
    finally:
        Log.set_stream(prev)
        Log.reset_level(level)
    assert "[Warning] obs test 7" in buf.getvalue()
    buf2 = io.StringIO()
    Log.set_stream(buf2)
    Log.set_stream(None)                   # None restores stderr
    Log.warning("not captured")
    assert buf2.getvalue() == ""


# -------------------------------------------------------- trace_summary

def test_trace_summary_reads_jsonl(tmp_path):
    path = tmp_path / "ev.jsonl"
    _train({"obs_memory_every": 2, "obs_timing": "phase"}, path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "trace_summary.py"),
                        str(path)], capture_output=True, text=True,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "per-phase time over 5 iterations (fenced)" in r.stdout
    assert "grow" in r.stdout and "tree_grow" in r.stdout
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "trace_summary.py"),
                        str(path), "--csv"], capture_output=True,
                       text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr
    rows = [ln.split(",") for ln in r.stdout.strip().splitlines()]
    assert rows[0] == ["kind", "name", "total_s", "mean_s", "count",
                      "extra"]
    kinds = {row[0] for row in rows[1:]}
    assert {"phase", "entry_compile", "entry_execute"} <= kinds


# ------------------------------------------------------------ bench --dry

def test_bench_dry_smoke():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                        "--dry"], capture_output=True, text=True, env=env,
                       cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("{")][-1]
    rec = json.loads(line)
    assert rec["status"] == "dry_ok"
    assert rec["iters"] == 5


# -------------------------------------------------------- overhead guard

def test_disabled_path_allocates_no_event_objects():
    """With telemetry off, training must not touch the obs subsystem:
    no observer construction, no fencing, no per-iteration allocations
    attributable to lightgbm_tpu/obs -- but for the span ring of
    obs/timers.py, which is always on and bounded: two records an
    iteration (`iteration`, `dispatch`)."""
    import tracemalloc
    X, y = _data()
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbose": -1},
                      train_set=lgb.Dataset(X, label=y))
    gbdt = bst._gbdt
    assert gbdt._obs is NULL_OBSERVER
    assert gbdt.learner._obs is NULL_OBSERVER
    gbdt.train_one_iter(None, None, False)      # compile outside the probe
    obs_dir = os.path.join(REPO, "lightgbm_tpu", "obs")
    tracemalloc.start()
    try:
        for _ in range(3):
            gbdt.train_one_iter(None, None, False)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    obs_allocs = snap.filter_traces(
        [tracemalloc.Filter(True, os.path.join(obs_dir, "*"))])
    by_file = {st.traceback[0].filename: st.size
               for st in obs_allocs.statistics("filename")}
    ring = by_file.pop(os.path.join(obs_dir, "timers.py"), 0)
    assert by_file == {}
    assert 0 < ring <= 3 * 2 * 1024
    assert NULL_OBSERVER.timeline == ()
    assert NULL_OBSERVER.entry_start() == 0.0
