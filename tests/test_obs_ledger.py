"""Cross-run performance ledger (obs/ledger.py) + the layers above it.

Covers:
  * run_header provenance (schema 10): emitted by RunObserver, required
    by strict validation for new-schema headers, absent-but-valid on
    old-schema records;
  * ingest — record shape, idempotent re-ingest (events and
    timelines), the comparability key (suite/shape/device);
  * crash-safety — corrupt index lines are skipped and the full run
    records under runs/ recover history the index lost;
  * rolling statistics — median/MAD with the noise floor, thin-history
    (< min) behavior, change-point detection on an injected step
    regression with git-rev attribution, `obs trend --check` exit
    semantics;
  * tools/bench_compare.py — the zero-baseline absolute-delta gate in
    both directions, and `--baseline rolling` (z-gate pass/fail,
    candidate-run exclusion, thin-history parent fallback notice).
"""
import importlib.util
import json
import os
import time

import pytest

from lightgbm_tpu.obs import SCHEMA_VERSION, read_events, validate_event
from lightgbm_tpu.obs.events import RunObserver, collect_provenance
from lightgbm_tpu.obs.ledger import (Ledger, change_points,
                                     comparable_entries,
                                     metrics_from_events,
                                     record_from_events, rolling_stats,
                                     sparkline)
from lightgbm_tpu.obs.query import main as obs_main

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

PROV = {"git_rev": "feedc0ffee12", "git_dirty": False,
        "hostname": "testhost", "argv": ["bench.py", "--dry"]}


def _events(run="r0", t=None, ips=5.0, first_s=1.5, git_rev=None,
            status="ok"):
    """A minimal finished-run event list with a deterministic rate."""
    t = time.time() if t is None else float(t)
    prov = dict(PROV, git_rev=git_rev or PROV["git_rev"])
    return [
        {"ev": "run_header", "run": run, "t": t,
         "schema": SCHEMA_VERSION, "backend": "cpu",
         "devices": [{"id": 0, "kind": "cpu"}], "provenance": prov,
         "context": {"tool": "bench"}},
        {"ev": "iter", "run": run, "t": t + 1, "it": 0,
         "time_s": 1.0 / ips},
        {"ev": "iter", "run": run, "t": t + 2, "it": 1,
         "time_s": 1.0 / ips},
        {"ev": "run_end", "run": run, "t": t + 3, "status": status,
         "entries": {"boost": {"first_s": first_s}}},
    ]


def _fill(led, n, ips=5.0, t0=1e9, suite="bench", **kw):
    for i in range(n):
        assert led.ingest_events(
            _events(run="r%03d" % i, t=t0 + 100 * i, ips=ips, **kw),
            suite=suite) == 1


# ------------------------------------------------------------ provenance

def test_run_header_carries_provenance(tmp_path):
    path = str(tmp_path / "tl.jsonl")
    obs = RunObserver(events_path=path)
    obs.run_header(backend="cpu", devices=["cpu:0"], params={},
                   context={})
    obs.close()
    header = next(e for e in read_events(path)
                  if e["ev"] == "run_header")
    prov = header["provenance"]
    assert set(prov) >= {"git_rev", "git_dirty", "hostname", "argv"}
    assert isinstance(prov["git_dirty"], bool)
    assert isinstance(prov["argv"], list)
    # this repo IS a git work tree, so the rev must resolve here
    assert prov["git_rev"]


def test_provenance_is_cached_and_refreshable():
    a, b = collect_provenance(), collect_provenance()
    assert a == b and a is not b          # copy out, same content
    assert collect_provenance(refresh=True) == a


def test_strict_validation_requires_provenance_on_new_schema():
    rec = {"ev": "run_header", "t": 0.0, "run": "r",
           "schema": SCHEMA_VERSION, "backend": "cpu", "devices": [],
           "params": {}, "context": {}, "timing": "iter"}
    with pytest.raises(ValueError, match="provenance"):
        validate_event(rec, strict=True)
    validate_event(dict(rec, provenance=PROV), strict=True)
    # pre-provenance schemas stay valid without it (old timelines load)
    validate_event(dict(rec, schema=9), strict=True)


# --------------------------------------------------------------- ingest

def test_ingest_record_shape(tmp_path):
    led = Ledger(str(tmp_path / "led"))
    assert led.ingest_events(_events(t=2e9), suite="bench") == 1
    (rec,) = led.entries()
    assert rec["suite"] == "bench"
    assert rec["device_kind"] == "cpu"
    assert rec["git_rev"] == PROV["git_rev"]
    assert rec["status"] == "ok"
    assert rec["metrics"]["iters_per_sec"] == pytest.approx(5.0)
    assert rec["metrics"]["compile_s"] == pytest.approx(1.5)


def test_ingest_is_idempotent(tmp_path):
    led = Ledger(str(tmp_path / "led"))
    evs = _events(t=2e9)
    assert led.ingest_events(evs, suite="bench") == 1
    assert led.ingest_events(evs, suite="bench") == 0
    assert len(led.entries()) == 1


def test_ingest_timeline_idempotent_and_skips_unfinished(tmp_path):
    path = str(tmp_path / "tl.jsonl")
    finished = _events(run="done", t=2e9)
    unfinished = _events(run="wip", t=2e9 + 50)[:-1]   # no run_end
    with open(path, "w") as f:
        for e in finished + unfinished:
            f.write(json.dumps(e) + "\n")
    led = Ledger(str(tmp_path / "led"))
    assert led.ingest_timeline(path, suite="bench") == 1
    assert led.ingest_timeline(path, suite="bench") == 0
    assert [r["run"] for r in led.entries()] == ["done"]


def test_metrics_from_events_matches_bench_compare(tmp_path):
    """The ledger's reducer and bench_compare's must agree — rolling
    baselines would otherwise gate candidates against skewed history."""
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(REPO, "tools", "bench_compare.py"))
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    evs = _events(t=2e9)
    assert metrics_from_events(evs) == bc._from_timeline(evs)


# ----------------------------------------------------------- crash-safety

def test_corrupt_index_line_recovery(tmp_path):
    led = Ledger(str(tmp_path / "led"))
    _fill(led, 3)
    # tear the middle index line (simulates a crash mid-append)
    with open(led.index_path) as f:
        lines = f.read().splitlines()
    lines[1] = lines[1][: len(lines[1]) // 2]
    with open(led.index_path, "w") as f:
        f.write("\n".join(lines) + "\n")
    entries = led.entries()
    # the torn run comes back from its runs/ record, nothing is lost
    assert sorted(r["run"] for r in entries) == ["r000", "r001", "r002"]
    # and re-ingesting it is still a no-op (dedup sees the recovery)
    assert led.ingest_events(_events(run="r001", t=1e9 + 100),
                             suite="bench") == 0


def test_missing_ledger_dir_reads_empty(tmp_path):
    led = Ledger(str(tmp_path / "never_created"))
    assert led.entries() == []


# ------------------------------------------------------ rolling statistics

def test_rolling_stats_median_mad_and_noise_floor():
    st = rolling_stats([10.0, 10.2, 9.8, 10.1, 9.9], window=8)
    assert st["median"] == pytest.approx(10.0)
    assert st["sigma"] >= 0.01 * 10.0     # never below the 1% floor
    flat = rolling_stats([5.0] * 6, window=8)
    assert flat["mad"] == 0.0
    assert flat["sigma"] == pytest.approx(0.05)   # 1% of the median
    assert rolling_stats([], window=8) is None


def test_rolling_window_trims_history():
    vals = [1.0] * 10 + [2.0] * 8
    st = rolling_stats(vals, window=8)
    assert st["n"] == 8 and st["median"] == 2.0


def test_comparable_entries_filters(tmp_path):
    led = Ledger(str(tmp_path / "led"))
    _fill(led, 3, suite="bench")
    assert led.ingest_events(_events(run="bad", t=5e9, status="aborted"),
                             suite="bench") == 1
    assert led.ingest_events(_events(run="other", t=6e9),
                             suite="serve") == 1
    entries = led.entries()
    comp = comparable_entries(entries, suite="bench",
                              metric="iters_per_sec")
    assert [r["run"] for r in comp] == ["r000", "r001", "r002"]
    # failed runs and foreign suites are out; exclusion drops self
    comp = comparable_entries(entries, suite="bench",
                              metric="iters_per_sec",
                              exclude_runs={"r001"})
    assert [r["run"] for r in comp] == ["r000", "r002"]


def test_trend_cells_key_on_world_size(tmp_path, capsys):
    """The backfill/gate guard (PR 14): a pod run is a DIFFERENT cell
    than single-host history — `obs trend --check` must not gate a
    2-rank run against 1-rank baselines, and comparable_entries must
    filter by world size."""
    led_dir = str(tmp_path / "led")
    led = Ledger(led_dir)
    _fill(led, 5, ips=5.0)               # 1-rank history
    # a 2-rank run at HALF the rate: against the 1-rank cell this is a
    # textbook >=3-MAD regression (test_obs_trend_check_exit_codes
    # proves exactly that shape trips the gate) — world_size keying
    # must keep it out of that cell entirely
    evs = _events(run="pod", t=1e9 + 900, ips=2.5,
                  git_rev="cafecafe1234")
    evs[0]["world_size"] = 2
    assert led.ingest_events(evs, suite="bench") == 1
    assert led.entries()[-1]["world_size"] == 2
    capsys.readouterr()
    assert obs_main(["trend", led_dir, "--check"]) == 0, \
        "2-rank run was gated against 1-rank history"

    entries = led.entries()
    comp2 = comparable_entries(entries, suite="bench",
                               metric="iters_per_sec", world_size=2)
    assert [r["run"] for r in comp2] == ["pod"]
    comp1 = comparable_entries(entries, suite="bench",
                               metric="iters_per_sec", world_size=1)
    assert "pod" not in [r["run"] for r in comp1] and len(comp1) == 5


def test_scaling_event_metrics_land_in_ledger(tmp_path):
    """bench.py --mp emits one `scaling` event (schema 12); the ledger
    must lift rows/sec/chip + weak-scaling efficiency out of it."""
    evs = _events(run="mp", t=1e9)
    evs[0]["world_size"] = 4
    sc = {"ev": "scaling", "run": "mp", "t": 1e9 + 2.5, "world_size": 4,
          "rows_per_sec_per_chip": 123.5, "efficiency": 0.91,
          "chips": 4, "mode": "weak"}
    assert validate_event(sc, strict=True) is sc   # schema-valid
    evs.insert(3, sc)
    m = metrics_from_events(evs)
    assert m["rows_per_sec_per_chip"] == 123.5
    assert m["weak_scaling_eff"] == 0.91
    from lightgbm_tpu.obs.ledger import METRIC_DIRECTIONS
    assert METRIC_DIRECTIONS["rows_per_sec_per_chip"] == 1
    assert METRIC_DIRECTIONS["weak_scaling_eff"] == 1

    led = Ledger(str(tmp_path / "led"))
    assert led.ingest_events(evs, suite="bench_mp") == 1
    rec = led.entries()[0]
    assert rec["world_size"] == 4
    assert rec["metrics"]["rows_per_sec_per_chip"] == 123.5


def test_change_point_on_injected_step(tmp_path):
    led = Ledger(str(tmp_path / "led"))
    _fill(led, 5, ips=5.0)
    # a >= 3-MAD step down, attributed to the run that introduced it
    assert led.ingest_events(
        _events(run="regress", t=1e9 + 900, ips=2.5,
                git_rev="badbadbad123"), suite="bench") == 1
    cps = change_points(led.entries(), "iters_per_sec")
    assert len(cps) == 1
    cp = cps[0]
    assert cp["run"] == "regress"
    assert cp["git_rev"] == "badbadbad123"
    assert cp["regression"] is True
    assert cp["z"] < -3.0


def test_change_point_needs_min_history(tmp_path):
    led = Ledger(str(tmp_path / "led"))
    _fill(led, 2, ips=5.0)
    assert led.ingest_events(_events(run="step", t=1e9 + 900, ips=2.5),
                             suite="bench") == 1
    assert change_points(led.entries(), "iters_per_sec",
                         min_history=3) == []


def test_recovery_supersedes_regression(tmp_path):
    """A later good-direction shift ends the bad regime: --check must
    not keep failing after the regression is fixed."""
    led = Ledger(str(tmp_path / "led"))
    _fill(led, 4, ips=5.0)
    for i, ips in enumerate([2.5] * 4 + [5.0] * 4):
        assert led.ingest_events(
            _events(run="s%d" % i, t=1e9 + 1000 + 100 * i, ips=ips),
            suite="bench") == 1
    cps = change_points(led.entries(), "iters_per_sec")
    assert [c["regression"] for c in cps] == [True, False]


def test_sparkline():
    assert sparkline([1, 2, 3]) == "▁▅█"
    assert sparkline([2.0, 2.0]) == "▄▄"
    assert sparkline([]) == ""


# -------------------------------------------------- obs history/trend CLI

def test_obs_trend_check_exit_codes(tmp_path, capsys):
    led_dir = str(tmp_path / "led")
    led = Ledger(led_dir)
    _fill(led, 5, ips=5.0)
    assert obs_main(["trend", led_dir, "--check"]) == 0
    assert led.ingest_events(
        _events(run="regress", t=1e9 + 900, ips=2.5,
                git_rev="badbadbad123"), suite="bench") == 1
    capsys.readouterr()
    assert obs_main(["trend", led_dir, "--check"]) == 1
    out = capsys.readouterr().out
    # the gate must NAME the metric, the onset run and its git rev
    assert "iters_per_sec" in out
    assert "regress" in out
    assert "badbadbad123" in out


def test_obs_history_renders(tmp_path, capsys):
    led_dir = str(tmp_path / "led")
    _fill(Ledger(led_dir), 3)
    assert obs_main(["history", led_dir]) == 0
    out = capsys.readouterr().out
    assert "bench" in out and "iters_per_sec" in out
    assert obs_main(["history", str(tmp_path / "empty")]) == 0
    assert "empty" in capsys.readouterr().out


# -------------------------------------------------- bench_compare gating

def _bench_compare():
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(REPO, "tools", "bench_compare.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_zero_baseline_gates_on_absolute_delta():
    bc = _bench_compare()
    # lower-is-better from zero: any increase regresses, finite delta
    rows = bc.compare({"serve_shed_rate": 0.0}, {"serve_shed_rate": 0.2},
                      {})
    (name, b, c, delta, regressed, _tol) = rows[0]
    assert regressed and delta == pytest.approx(0.2)
    # higher-is-better from zero: a DROP regresses too (the old ratio
    # guard only caught the lower-is-better sign)
    rows = bc.compare({"final_eval_metric": 0.0},
                      {"final_eval_metric": -0.5}, {})
    assert rows[0][4] is True and rows[0][3] == pytest.approx(-0.5)
    # ... and matching zeros pass both ways
    for metric in ("serve_shed_rate", "final_eval_metric"):
        rows = bc.compare({metric: 0.0}, {metric: 0.0}, {})
        assert rows[0][4] is False and rows[0][3] == 0.0
    # epsilon widens the zero-baseline gate
    rows = bc.compare({"serve_shed_rate": 0.0}, {"serve_shed_rate": 0.1},
                      {}, zero_eps={"serve_shed_rate": 0.15})
    assert rows[0][4] is False


def test_zero_baseline_json_is_finite(tmp_path, capsys):
    bc = _bench_compare()
    base = tmp_path / "base.jsonl"
    cand = tmp_path / "cand.jsonl"
    base.write_text(json.dumps({"metric": "x", "value": 1.0,
                                "unit": "iters/sec",
                                "serve_shed_rate": 0.0}) + "\n")
    cand.write_text(json.dumps({"metric": "x", "value": 1.0,
                                "unit": "iters/sec",
                                "serve_shed_rate": 0.25}) + "\n")
    assert bc.main([str(base), str(cand), "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)   # inf would not parse
    row = next(m for m in doc["metrics"]
               if m["metric"] == "serve_shed_rate")
    assert row["regressed"] and row["delta_kind"] == "abs"
    assert row["delta_frac"] == pytest.approx(0.25)


def _candidate_timeline(path, ips):
    with open(path, "w") as f:
        for e in _events(run="cand", t=3e9, ips=ips):
            f.write(json.dumps(e) + "\n")
    return str(path)


def test_rolling_mode_gates_against_ledger(tmp_path, capsys):
    bc = _bench_compare()
    led_dir = str(tmp_path / "led")
    _fill(Ledger(led_dir), 5, ips=5.0)
    ok = _candidate_timeline(tmp_path / "ok.jsonl", 4.95)
    bad = _candidate_timeline(tmp_path / "bad.jsonl", 2.0)
    args = ["--baseline", "rolling", "--ledger", led_dir,
            "--suite", "bench"]
    assert bc.main([ok, ok] + args) == 0
    capsys.readouterr()
    assert bc.main([ok, bad] + args + ["--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["mode"] == "rolling"
    row = next(m for m in doc["metrics"]
               if m["metric"] == "iters_per_sec")
    assert row["gate"] == "rolling" and row["z"] < -3.0
    assert row["baseline"] == pytest.approx(5.0)   # the rolling median


def test_rolling_mode_excludes_candidate_run(tmp_path):
    """A candidate already ingested (the observer lands runs on close)
    must not dilute its own baseline."""
    bc = _bench_compare()
    led_dir = str(tmp_path / "led")
    led = Ledger(led_dir)
    _fill(led, 3, ips=5.0)
    bad = _candidate_timeline(tmp_path / "bad.jsonl", 2.0)
    assert led.ingest_timeline(bad, suite="bench") == 1
    assert bc.main([bad, bad, "--baseline", "rolling", "--ledger",
                    led_dir, "--suite", "bench"]) == 1


def test_rolling_mode_thin_history_falls_back_to_parent(tmp_path,
                                                        capsys):
    bc = _bench_compare()
    led_dir = str(tmp_path / "led")
    _fill(Ledger(led_dir), 2, ips=5.0)          # < --min-history 3
    base = _candidate_timeline(tmp_path / "base.jsonl", 5.0)
    slow = _candidate_timeline(tmp_path / "slow.jsonl", 2.0)
    capsys.readouterr()
    assert bc.main([base, slow, "--baseline", "rolling", "--ledger",
                    led_dir, "--suite", "bench"]) == 1
    err = capsys.readouterr().err
    assert "falling back to parent compare" in err
    # parent says ok -> thin-history rolling says ok too
    assert bc.main([base, base, "--baseline", "rolling", "--ledger",
                    led_dir, "--suite", "bench"]) == 0


def test_rolling_mode_derives_cell_from_candidate(tmp_path, capsys):
    """Without --suite/--shape the gate scopes to the candidate's own
    ledger cell (suite from the header, device kind always) instead of
    pooling every run in the store."""
    bc = _bench_compare()
    led_dir = str(tmp_path / "led")
    led = Ledger(led_dir)
    _fill(led, 5, ips=5.0)                       # suite "bench", cpu
    bad = _candidate_timeline(tmp_path / "bad.jsonl", 2.0)
    # derived suite matches the history -> z-gates and fails, no flag
    assert bc.main([bad, bad, "--baseline", "rolling",
                    "--ledger", led_dir]) == 1
    # history in a foreign suite must not score this candidate: thin
    # in its own cell -> parent fallback -> self-compare passes
    led2_dir = str(tmp_path / "led2")
    _fill(Ledger(led2_dir), 5, ips=5.0, suite="other")
    capsys.readouterr()
    assert bc.main([bad, bad, "--baseline", "rolling",
                    "--ledger", led2_dir]) == 0
    assert "falling back to parent compare" in capsys.readouterr().err
    # same suite on a different device kind is equally incomparable
    led3_dir = str(tmp_path / "led3")
    led3 = Ledger(led3_dir)
    for i in range(5):
        evs = _events(run="tpu%03d" % i, t=1e9 + 100 * i, ips=5.0)
        evs[0]["backend"] = "tpu"
        evs[0]["devices"] = [{"id": 0, "kind": "tpu"}]
        assert led3.ingest_events(evs, suite="bench") == 1
    assert bc.main([bad, bad, "--baseline", "rolling",
                    "--ledger", led3_dir]) == 0


def test_rolling_mode_missing_ledger_is_thin_not_fatal(tmp_path):
    bc = _bench_compare()
    base = _candidate_timeline(tmp_path / "base.jsonl", 5.0)
    assert bc.main([base, base, "--baseline", "rolling", "--ledger",
                    str(tmp_path / "nothing")]) == 0


def test_observer_ingests_on_clean_close(tmp_path):
    """The automatic wiring: RunObserver(ledger_dir=...) lands the run
    when (and only when) it closes clean."""
    led_dir = str(tmp_path / "led")
    path = str(tmp_path / "tl.jsonl")
    obs = RunObserver(events_path=path, ledger_dir=led_dir,
                      ledger_suite="unit")
    obs.run_header(backend="cpu", devices=["cpu:0"], params={},
                   context={})
    obs.event("iter", it=0, time_s=0.5, fenced=True, phases={})
    obs.close()
    (rec,) = Ledger(led_dir).entries()
    assert rec["suite"] == "unit" and rec["run"] == obs.run_id
    # an aborted run must NOT land
    obs2 = RunObserver(events_path=str(tmp_path / "tl2.jsonl"),
                       ledger_dir=led_dir, ledger_suite="unit")
    obs2.run_header(backend="cpu", devices=["cpu:0"], params={},
                    context={})
    obs2.event("iter", it=0, time_s=0.5, fenced=True, phases={})
    obs2.close(status="aborted")
    assert len(Ledger(led_dir).entries()) == 1
