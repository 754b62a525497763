"""BENCHMARK.json against the files it names, and the harness as data: a
cell, a configuration and a per-layer metric are added as new files and
new entries, with no edit to a file that is there."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.files import HERE, ROOT, load_json, load_module

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    assert os.path.isfile(os.path.join(ROOT, BENCH["command"][1]))
    assert BENCH["command"][1].startswith(tuple(BENCH["paths"]))


@pytest.mark.parametrize("name", sorted(
    [c["name"] for c in BENCH["configs"]]
    + [w[k] for w in BENCH["workloads"] for k in ("name", "config", "traffic")]
    + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    + [k for c in BENCH["configs"] for k in c["reduced"]]))
def test_names_hold_only_the_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in BENCH["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        assert callable(load_module("metrics", metric["name"]).read)


def test_names_are_unique_and_setup_s_is_there():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in _metrics()]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    config = load_json(os.path.join(ROOT, entry["file"]))
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    # no width is cut: the columns, bins and leaves are the published ones
    assert config["columns"] == config["published"]["columns"]
    assert config["params"]["max_bin"] == 63
    assert config["params"]["num_leaves"] == 255
    assert not [k for k in config["params"]
                if k.startswith(("tpu_", "obs_"))]
    assert {"rows", "data"} <= set(config["assumed"])
    assert set(config["limits"]) == {"count_mismatch", "leaf_value_gap_step0",
                                     "score_gap_step0",
                                     "split_gain_gap_step0", "loss_gap"}
    assert callable(load_module("references", config["reference"]).follow)
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    assert callable(load_module("drivers", traffic["driver"]).run)
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}


THROWAWAY_METRIC = '''
def read(run):
    return float(len(run["iter_seconds"]))
'''


def test_a_cell_a_configuration_and_a_metric_are_added_as_files(tmp_path):
    """Copy the benchmark, add files and entries and edit nothing: the new
    cell runs, reports the new metric, and the old entries still stand."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "lightgbm_tpu"), root / "lightgbm_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    config = load_json(os.path.join(ROOT, "benchmark", "configs",
                                    "epsilon_2000.json"))
    config.update(name="throwaway_40", rows=6000, columns=40, shard_rows=4096)
    config["params"]["num_leaves"] = 15
    (root / "benchmark/configs/throwaway_40.json").write_text(
        json.dumps(config))
    traffic = load_json(os.path.join(HERE, "traffic", "train.json"))
    traffic.update(check_nodes=4, trace_iterations=2)
    (root / "benchmark/traffic/throwaway.json").write_text(
        json.dumps(traffic))
    (root / "benchmark/metrics/throwaway_iters.py").write_text(
        THROWAWAY_METRIC)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "throwaway_40", "source": config["source"],
        "file": "benchmark/configs/throwaway_40.json",
        "reduced": ["rows"], "why": "a test"})
    bench["workloads"].append({
        "name": "throwaway_cell", "config": "throwaway_40",
        "traffic": "throwaway", "chips": 1, "why": "a test"})
    for metric in bench["per_layer"]:
        if metric["name"] != "iter_mfu":    # no peaks for a CPU: an error
            metric["workloads"].append("throwaway_cell")
    bench["per_layer"].append({
        "name": "throwaway_iters", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "iteration",
        "moves": "iters_per_s", "workloads": ["throwaway_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    code = ("import sys; sys.path.insert(0, %r); import jax; "
            "import benchmark.run as r; "
            "r.main(['--workload', 'throwaway_cell', '--seed', '4', "
            "'--seconds', '0.3', '--trace', sys.argv[1]], "
            "devices=jax.devices()[:1])" % str(root))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    lines = {}
    for trace in ("0", "1"):
        proc = subprocess.run([sys.executable, "-c", code, trace], cwd=root,
                              env=env, capture_output=True, text=True,
                              timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines[trace] = json.loads(proc.stdout.strip().splitlines()[-1])
        assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    plain, traced = lines["0"], lines["1"]
    assert plain["correct"] and traced["correct"]
    assert list(plain)[-1] == "checks"
    assert set(plain["metrics"]) == {"iters_per_s", "peak_hbm_gib", "setup_s"}
    assert traced["metrics"]["throwaway_iters"]["value"] == 2.0
    assert {"construct_s", "booster_init_s", "first_iter_s",
            "iter_p50_ms"} <= set(traced["metrics"])
    # off the chip the trace has no device plane: such metrics are left out
    assert "hist_roofline" not in traced["metrics"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(plain["device"])
    after = {p: p.read_bytes() for p in before}
    assert after == before
