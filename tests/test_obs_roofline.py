"""Roofline attribution (obs/roofline.py, schema 13).

Covers the device-peak registry (table lookup, alias/prefix resolution,
the unknown-kind error, JSON overrides), the per-entry roofline
join and its bound classification edges (compute / memory / collective /
host-orchestration, the ORCH_FLOOR regime), the per-iteration
``utilization`` rollup math and its end-to-end emission from a real
training run, the ``obs roofline`` CLI and its ``--check`` exit codes,
an older run's tuner events (accepted, rendered by nothing), the
serving-tier executable
join, the humanized ``obs recompiles`` cost tags, the shared
``parse_compiled`` helper both the JIT tracker and the serve tier read
XLA analyses through (list-form ``cost_analysis`` regression), and the
ledger / bench_compare lockstep extraction of ``flop_util`` /
``hbm_util``.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import SCHEMA_VERSION, read_events, validate_event
from lightgbm_tpu.obs.compile import analyze_compiled, parse_compiled
from lightgbm_tpu.obs.ledger import metrics_from_events
from lightgbm_tpu.obs.query import main as obs_main
from lightgbm_tpu.obs.roofline import (BOUNDS, DEFAULT_PEAKS, ORCH_FLOOR,
                                       entry_roofline, fmt_bytes,
                                       fmt_quantity, load_peak_overrides,
                                       normalize_kind, peaks_for,
                                       timeline_roofline,
                                       utilization_rollup)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# a known-profile peak set for exact-math assertions: 100 GFLOP/s,
# 25 GB/s HBM, 10 GB/s ICI (the built-in cpu row)
CPU_PEAKS = dict(DEFAULT_PEAKS["cpu"], kind="cpu", source="table")

PROV = {"git_rev": "feedc0ffee12", "git_dirty": False,
        "hostname": "testhost", "argv": ["bench.py", "--dry"]}


def _header(run="r0", t=1e9, kind="cpu", **kw):
    return dict({"ev": "run_header", "run": run, "t": t,
                 "schema": SCHEMA_VERSION, "backend": "cpu",
                 "devices": [{"id": 0, "kind": kind}], "params": {},
                 "context": {}, "timing": "iter", "provenance": PROV},
                **kw)


def _attr(entry, cost, run="r0", t=1e9):
    return {"ev": "compile_attr", "run": run, "t": t + 1, "entry": entry,
            "n_compiles": 1, "sig": {}, "cost": cost}


def _end(entries, run="r0", t=1e9):
    return {"ev": "run_end", "run": run, "t": t + 9, "iters": 2,
            "phase_totals": {}, "entries": entries, "status": "ok"}


def _write(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    read_events(path)                    # must be schema-valid
    return str(path)


# -------------------------------------------------- peak registry

def test_normalize_kind_and_aliases():
    assert normalize_kind("TPU v4") == "tpu_v4"
    assert normalize_kind("TPU-v5p") == "tpu_v5p"
    assert normalize_kind("tpu_v5e") == "tpu_v5_lite"
    assert normalize_kind("TPU v6e") == "tpu_v6_lite"
    assert normalize_kind("") == ""


def test_peaks_exact_prefix_and_unknown_kind_raises():
    p = peaks_for("TPU v4")
    assert p["kind"] == "tpu_v4" and p["source"] == "table"
    assert p["flops_bf16"] == DEFAULT_PEAKS["tpu_v4"]["flops_bf16"]
    # the string a v5e chip reports (jax.devices()[0].device_kind)
    assert peaks_for("TPU v5 lite")["kind"] == "tpu_v5_lite"
    # prefix resolution: a pod-suffixed kind still finds its generation
    assert peaks_for("tpu_v5p_pod")["kind"] == "tpu_v5p"
    # the CPU backend keeps its own row for CPU tests
    assert peaks_for("cpu")["kind"] == "cpu"
    # a device that is not in the table is an error, not a default
    for unknown in ("warp_drive_9000", ""):
        with pytest.raises(ValueError, match="no roofline peaks"):
            peaks_for(unknown)
    # every profile carries the full field set
    for prof in DEFAULT_PEAKS.values():
        assert set(prof) == {"flops_f32", "flops_bf16", "hbm_bytes_per_s",
                             "ici_bytes_per_s", "vmem_bytes"}


def test_peak_overrides_merge_over_defaults(tmp_path):
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps({
        "TPU v4": {"hbm_bytes_per_s": 999e9},
        "mychip": {"flops_f32": 1e12},
    }))
    ov = load_peak_overrides(str(path))
    p = peaks_for("tpu_v4", ov)
    assert p["source"] == "override"
    assert p["hbm_bytes_per_s"] == 999e9
    # un-overridden fields keep the table figure (merge, not replace)
    assert p["flops_f32"] == DEFAULT_PEAKS["tpu_v4"]["flops_f32"]
    # a kind the table lacks has no base profile to merge over: an
    # override that leaves fields out is an error
    with pytest.raises(ValueError, match="lack"):
        peaks_for("mychip", ov)
    full = {"mychip": dict(DEFAULT_PEAKS["cpu"], flops_f32=1e12)}
    q = peaks_for("mychip", full)
    assert q["source"] == "override" and q["flops_f32"] == 1e12


def test_unreadable_overrides_warn_and_disable(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert load_peak_overrides(str(bad)) == {}
    assert load_peak_overrides("") == {}
    assert load_peak_overrides(str(tmp_path / "absent.json")) == {}


# -------------------------------------------------- per-entry join

def test_memory_bound_entry():
    # 25e6 B at 25 GB/s -> 1 ms memory roof; 2 ms measured -> 50% HBM
    r = entry_roofline({"flops": 1e6, "bytes_accessed": 25e6},
                       2e-3, 10, CPU_PEAKS)
    assert r["bound"] == "memory"
    assert r["hbm_util"] == pytest.approx(0.5)
    assert r["flop_util"] == pytest.approx(0.005)
    assert r["achieved_bytes_per_s"] == pytest.approx(12.5e9)
    assert r["ai"] == pytest.approx(1e6 / 25e6)
    # headroom: (2 ms - 1 ms) x 10 calls = 10 ms recoverable
    assert r["headroom_s"] == pytest.approx(1e-2)


def test_compute_bound_entry_and_util_cap():
    # 100e6 FLOP at 100 GFLOP/s -> 1 ms compute roof
    r = entry_roofline({"flops": 100e6, "bytes_accessed": 1e3},
                       2e-3, 1, CPU_PEAKS)
    assert r["bound"] == "compute"
    assert r["flop_util"] == pytest.approx(0.5)
    # measured faster than the model's roof: utilization clips at 1.0
    fast = entry_roofline({"flops": 100e6, "bytes_accessed": 1e3},
                          1e-4, 1, CPU_PEAKS)
    assert fast["flop_util"] == 1.0 and fast["headroom_s"] == 0.0


def test_collective_bound_needs_world_size():
    cost = {"flops": 1e3, "bytes_accessed": 1e3}
    # 10e6 ICI bytes at 10 GB/s -> 1 ms; dominates at world_size > 1
    r = entry_roofline(cost, 2e-3, 1, CPU_PEAKS, ici_bytes=10e6,
                      world_size=2)
    assert r["bound"] == "collective"
    assert r["ici_util"] == pytest.approx(0.5)
    # single-process runs ignore ICI byte estimates entirely
    r1 = entry_roofline(cost, 2e-3, 1, CPU_PEAKS, ici_bytes=10e6,
                        world_size=1)
    assert "ici_util" not in r1 and r1["bound"] != "collective"


def test_host_orchestration_floor():
    # just above the floor on the memory roof -> still memory-bound
    near = entry_roofline(
        {"flops": 0.0, "bytes_accessed": (ORCH_FLOOR + 0.001) * 25e9},
        1.0, 1, CPU_PEAKS)
    assert near["bound"] == "memory"
    # under the floor on EVERY roof -> the time bought dispatch glue
    r = entry_roofline(
        {"flops": 0.0, "bytes_accessed": (ORCH_FLOOR - 0.001) * 25e9},
        1.0, 1, CPU_PEAKS)
    assert r["bound"] == "host-orchestration"
    # no cost estimate at all: zero utilization, host-orchestration
    none = entry_roofline(None, 1e-3, 5, CPU_PEAKS)
    assert none["bound"] == "host-orchestration"
    assert none["flop_util"] == 0.0 and none["ai"] is None
    assert {r["bound"], near["bound"], none["bound"]} <= set(BOUNDS)


def test_zero_exec_time_is_safe():
    r = entry_roofline({"flops": 1e6, "bytes_accessed": 1e6}, 0.0, 0,
                       CPU_PEAKS)
    assert r["flop_util"] == 0.0 and r["headroom_s"] == 0.0
    assert r["bound"] == "host-orchestration"


# -------------------------------------------------- timeline join

def _timeline(kind="cpu"):
    return [
        _header(kind=kind),
        _attr("tree_grow", {"flops": 1e6, "bytes_accessed": 25e6}),
        _end({
            # memory-bound with 1 ms headroom per call, 10 calls
            "tree_grow": {"exec_mean_s": 2e-3, "exec_n": 10,
                          "exec_total_s": 2e-2, "first_s": 0.5},
            # timed entry XLA never modelled: host-orchestration
            "boost": {"exec_mean_s": 1e-4, "exec_n": 10,
                      "exec_total_s": 1e-3, "first_s": 0.1},
        }),
    ]


def test_timeline_roofline_ranks_by_headroom():
    res = timeline_roofline(_timeline())
    assert res["problems"] == []
    assert res["device_kind"] == "cpu"
    assert res["peaks"]["kind"] == "cpu"
    rows = res["rows"]
    assert [r["entry"] for r in rows] == ["tree_grow", "boost"]
    grow, boost = rows
    assert grow["has_cost"] and grow["bound"] == "memory"
    assert grow["headroom_s"] == pytest.approx(1e-2)
    assert not boost["has_cost"]
    assert boost["bound"] == "host-orchestration"
    assert boost["exec_total_s"] == pytest.approx(1e-3)


def test_last_compile_attr_cost_wins():
    evs = _timeline()
    # a later recompile supersedes the warmup program's estimate
    evs.insert(2, _attr("tree_grow", {"flops": 5e7,
                                      "bytes_accessed": 1e3}, t=2e9))
    row = timeline_roofline(evs)["rows"][0]
    assert row["entry"] == "tree_grow"
    assert row["flops"] == 5e7 and row["bound"] == "compute"


def test_timeline_problems():
    # no run_end at all: nothing to attribute
    res = timeline_roofline([_header()])
    assert any("run_end" in p for p in res["problems"])
    # timed entries but zero cost estimates: tell them to turn on
    # obs_compile rather than rendering an all-orchestration table
    evs = [_header(), _end({"tree_grow": {"exec_mean_s": 1e-3,
                                          "exec_n": 2,
                                          "exec_total_s": 2e-3}})]
    res = timeline_roofline(evs)
    assert any("obs_compile" in p for p in res["problems"])


# -------------------------------------------------- utilization rollup

def test_utilization_rollup_weighted_mean():
    summary = {
        # hbm_util 0.5, weight 1.0 s, headroom 0.5 s
        "a": {"exec_mean_s": 1.0, "exec_n": 1, "exec_total_s": 1.0},
        # hbm_util 0.1, weight 3.0 s, headroom 2.7 s (the worst)
        "b": {"exec_mean_s": 3.0, "exec_n": 1, "exec_total_s": 3.0},
    }
    costs = {"a": {"flops": 1.0, "bytes_accessed": 12.5e9},
             "b": {"flops": 1.0, "bytes_accessed": 7.5e9}}
    roll = utilization_rollup(summary, costs, CPU_PEAKS)
    assert roll["hbm_util"] == pytest.approx((0.5 * 1 + 0.1 * 3) / 4.0)
    assert roll["headroom_s"] == pytest.approx(0.5 + 2.7)
    assert roll["bound"] == "memory"          # the worst entry's bound
    assert roll["device_kind"] == "cpu"
    assert roll["roof_source"] == "table"
    assert set(roll["entries"]) == {"a", "b"}
    assert roll["entries"]["a"]["hbm_util"] == pytest.approx(0.5)
    assert all(v["bound"] in BOUNDS for v in roll["entries"].values())


def test_rollup_none_without_costs():
    summary = {"a": {"exec_mean_s": 1.0, "exec_n": 1,
                     "exec_total_s": 1.0}}
    assert utilization_rollup(summary, {}, CPU_PEAKS) is None
    assert utilization_rollup({}, {"a": {"flops": 1.0}}, CPU_PEAKS) is None
    # entries without a cost estimate are skipped, not zero-averaged
    roll = utilization_rollup(
        dict(summary, b={"exec_mean_s": 9.0, "exec_n": 1,
                         "exec_total_s": 9.0}),
        {"a": {"flops": 1.0, "bytes_accessed": 12.5e9}}, CPU_PEAKS)
    assert set(roll["entries"]) == {"a"}


def test_utilization_event_emitted_from_training(tmp_path):
    """End to end: obs_utilization_every rides the iter path, implies
    the compile tracker, and every rollup validates under schema 13."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(500, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    path = str(tmp_path / "tl.jsonl")
    lgb.train({"objective": "binary", "num_leaves": 7, "max_bin": 15,
               "verbose": -1, "obs_events_path": path,
               "obs_timing": "iter", "obs_utilization_every": 2},
              lgb.Dataset(X, label=y), num_boost_round=4)
    evs = read_events(path)              # validates every record
    header = next(e for e in evs if e["ev"] == "run_header")
    assert header["schema"] == SCHEMA_VERSION >= 13
    utils = [e for e in evs if e["ev"] == "utilization"]
    assert utils, "obs_utilization_every=2 emitted no rollups"
    assert [u["it"] for u in utils] == [0, 2]
    for u in utils:
        assert 0.0 <= u["flop_util"] <= 1.0
        assert 0.0 <= u["hbm_util"] <= 1.0
        assert u["bound"] in BOUNDS
        assert u["entries"]
        assert all(v["bound"] in BOUNDS for v in u["entries"].values())
        assert u["roof_source"] in ("table", "override")
        assert u["device_kind"]
    # the timeline must also satisfy the CLI gate it feeds in CI
    assert obs_main(["roofline", path, "--check"]) == 0


# -------------------------------------------------- obs roofline CLI

def test_cli_renders_table_and_passes_check(tmp_path, capsys):
    p = _write(tmp_path / "tl.jsonl", _timeline())
    assert obs_main(["roofline", p]) == 0
    out = capsys.readouterr().out
    assert "== roofline: cpu" in out
    assert "tree_grow" in out and "boost" in out
    assert "(no cost estimate)" in out      # the boost entry's suffix
    assert "memory" in out and "host-orchestration" in out
    assert "total headroom" in out and "bound mix" in out
    assert obs_main(["roofline", p, "--check"]) == 0


def test_cli_check_exit_codes(tmp_path, capsys):
    # structurally unusable timelines fail the gate with exit 1 ...
    no_cost = _write(tmp_path / "nc.jsonl", [
        _header(), _end({"tree_grow": {"exec_mean_s": 1e-3, "exec_n": 2,
                                       "exec_total_s": 2e-3}})])
    assert obs_main(["roofline", no_cost, "--check"]) == 1
    assert "PROBLEM" in capsys.readouterr().out
    no_end = _write(tmp_path / "ne.jsonl", [_header()])
    assert obs_main(["roofline", no_end, "--check"]) == 1
    # ... but render informationally without --check
    assert obs_main(["roofline", no_cost]) == 0
    # and a missing file is a usage error, matching the other subcommands
    assert obs_main(["roofline", str(tmp_path / "nope.jsonl")]) == 2


def test_cli_peaks_override(tmp_path, capsys):
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"hbm_bytes_per_s": 50e9}}))
    p = _write(tmp_path / "tl.jsonl", _timeline())
    assert obs_main(["roofline", p, "--peaks", str(peaks)]) == 0
    out = capsys.readouterr().out
    assert "override peaks" in out
    assert "50.00 GB" in out.replace("GiB", "GB") or "46.57 GiB" in out


# ----------------------------- event kinds that nothing emits any more

def test_explain_accepts_an_older_runs_tuner_events(tmp_path, capsys):
    """The measured kernel tuner is deleted and nothing emits its two
    event kinds (schema 8, with schema 13's roofline stamp), but a
    timeline written by an older run is input from outside: each kind
    still validates with every field the table declares for it, and
    `obs explain` reads past them."""
    from lightgbm_tpu.obs.events import _REQUIRED, declared_fields
    kinds = sorted(ev for ev in _REQUIRED if ev.startswith("auto"))
    assert len(kinds) == 2
    old = [dict({f: {} for f in declared_fields(ev)},
                ev=ev, run="r0", t=1e9 + 1 + i)
           for i, ev in enumerate(kinds)]
    for e in old:
        validate_event(e, strict=True)
    p = _write(tmp_path / "tl.jsonl", [_header()] + old + [_end({})])
    assert obs_main(["explain", p]) == 0
    out = capsys.readouterr().out
    assert kinds[0] not in out and "no model/data events" in out


# -------------------------------------------------- serve tier

def test_serve_roofline_joins_bucket_executables():
    from lightgbm_tpu.obs.serve import serve_roofline
    evs = [
        _header(),
        _attr("serve_predict_b256", {"flops": 1e6,
                                     "bytes_accessed": 25e6}),
        _attr("serve_predict_b512_conv", {"flops": 1e6,
                                          "bytes_accessed": 1e6}),
        {"ev": "serve_batch", "run": "r0", "t": 1e9 + 2,
         "route": "predict", "rows": 200, "bucket": 256, "pad": 56,
         "requests": 1, "queue_s": 1e-4, "exec_s": 2e-3},
    ]
    rows = serve_roofline(evs)
    by_entry = {r["entry"]: r for r in rows}
    timed = by_entry["serve_predict_b256"]
    assert timed["timed"] and timed["bucket"] == 256
    assert timed["hbm_util"] == pytest.approx(0.5)
    assert timed["bound"] == "memory"
    untimed = by_entry["serve_predict_b512_conv"]
    assert not untimed["timed"] and untimed["bucket"] == 512
    assert untimed["exec_n"] == 0
    # non-serve timelines produce no rows (the report section is absent)
    assert serve_roofline([_header()]) == []


# -------------------------------------------------- recompiles units

def test_recompiles_humanized_cost_tags(tmp_path, capsys):
    p = _write(tmp_path / "tl.jsonl", [
        _header(),
        _attr("tree_grow", {"flops": 2.5e9,
                            "bytes_accessed": 3 * 2**20}),
        _end({}),
    ])
    assert obs_main(["recompiles", p]) == 0
    out = capsys.readouterr().out
    assert "2.50 GFLOP" in out and "3.00 MiB" in out
    assert "2500000000" not in out          # no raw-unit spelunking


def test_fmt_helpers():
    assert fmt_quantity(2.5e9, "FLOP") == "2.50 GFLOP"
    assert fmt_quantity(1e3) == "1.00 K"
    assert fmt_quantity(12) == "12"
    assert fmt_quantity(3.2e13, "FLOP") == "32.00 TFLOP"
    assert fmt_bytes(3 * 2**20) == "3.00 MiB"
    assert fmt_bytes(512) == "512 B"
    assert fmt_bytes(1.5 * 2**30) == "1.50 GiB"


# -------------------------------------------------- shared cost parser

class _FakeCompiled:
    """cost_analysis in the LIST form recent jax CPU backends return."""

    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        return self._ca

    def memory_analysis(self):
        raise NotImplementedError


class _FakeJitted:
    def __init__(self, compiled):
        self._compiled = compiled

    def lower(self, *args, **kw):
        return self

    def compile(self):
        return self._compiled


def test_parse_compiled_handles_list_and_dict_forms():
    want = {"cost": {"flops": 5.0, "bytes_accessed": 7.0}}
    listed = _FakeCompiled([{"flops": 5.0, "bytes accessed": 7.0}])
    assert parse_compiled(listed) == want
    bare = _FakeCompiled({"flops": 5.0, "bytes accessed": 7.0})
    assert parse_compiled(bare) == want
    assert parse_compiled(_FakeCompiled([])) == {}
    # the JIT call site reads through the same parser
    assert analyze_compiled(_FakeJitted(listed), (1,)) == want


def test_serve_executable_uses_shared_parser():
    """Regression guard for the dedup: serve/executable.py must read
    XLA analyses through obs/compile.parse_compiled rather than a
    private copy (the list-form quirk is handled exactly once)."""
    from lightgbm_tpu.serve import executable
    assert executable.parse_compiled is parse_compiled
    assert not hasattr(executable, "_compiled_analysis")


# -------------------------------------------------- ledger lockstep

def _util_timeline():
    t = 1e9
    return [
        _header(t=t),
        {"ev": "iter", "run": "r0", "t": t + 1, "it": 0, "time_s": 0.5,
         "phases": {}, "fenced": True},
        {"ev": "iter", "run": "r0", "t": t + 2, "it": 1, "time_s": 0.5,
         "phases": {}, "fenced": True},
        {"ev": "utilization", "run": "r0", "t": t + 3, "it": 0,
         "flop_util": 0.9, "hbm_util": 0.9, "bound": "memory",
         "entries": {"tree_grow": {"bound": "memory"}}},
        # the LAST rollup is the steady-state figure readers keep
        {"ev": "utilization", "run": "r0", "t": t + 4, "it": 1,
         "flop_util": 0.25, "hbm_util": 0.5, "bound": "memory",
         "entries": {"tree_grow": {"bound": "memory"}}},
        _end({}, t=t),
    ]


def test_ledger_reads_last_utilization_rollup():
    m = metrics_from_events(_util_timeline())
    assert m["flop_util"] == pytest.approx(0.25)
    assert m["hbm_util"] == pytest.approx(0.5)
    # and both are gated metric directions (higher is better)
    from lightgbm_tpu.obs.ledger import METRIC_DIRECTIONS
    assert METRIC_DIRECTIONS["flop_util"] == +1
    assert METRIC_DIRECTIONS["hbm_util"] == +1


def test_bench_compare_extracts_utilization_in_lockstep(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import bench_compare
    finally:
        sys.path.pop(0)
    p = _write(tmp_path / "tl.jsonl", _util_timeline())
    m = bench_compare._from_timeline(read_events(p))
    assert m["flop_util"] == pytest.approx(0.25)
    assert m["hbm_util"] == pytest.approx(0.5)
    assert bench_compare.METRICS["flop_util"][0] == +1
    assert bench_compare.METRICS["hbm_util"][0] == +1
    # self-compare must pass with the new gated metrics present
    assert bench_compare.main([p, p]) == 0
