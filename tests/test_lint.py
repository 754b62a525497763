"""graftlint static analyzer (lightgbm_tpu/analysis/).

Per-pass fixture coverage — one true positive and one true negative for
each of the five passes — plus the suppression/baseline machinery and
the CLI exit-code contract (0 clean / 1 findings / 2 internal error,
the bench_compare convention).  The repo-clean gate itself
(`python -m lightgbm_tpu lint --check` exits 0 on this tree) runs both
here and as the CI lint job.

Regression tests for the true positives the analyzer surfaced when it
first ran live next to the fixtures:

* pallas_hist's row-chunk floor (512) silently oversubscribed the tile
  budget at B>=1024 — now floor 128 + `supports_bins` + onehot fallback
  (vmem-hist-tile).
* the deliberate hot-path readbacks (stop check, tree materialization,
  prediction drain, serve execute) used bare `jax.device_get`,
  invisible to the fence_count() sync audit — now obs/timers.fenced_get
  (sync-device-get).
"""
import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lightgbm_tpu.analysis import core
from lightgbm_tpu.analysis import (config_coherence, events_schema,
                                   hostsync, recompile, vmem)
from lightgbm_tpu.analysis.cli import main as lint_main

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mod_from(src, path="lightgbm_tpu/ops/fixture.py"):
    """Build a SourceModule the way load_modules does, from a string."""
    tree = ast.parse(src, filename=path)
    return core.SourceModule(path, src, tree, src.splitlines())


def run_pass(p, src, path="lightgbm_tpu/ops/fixture.py"):
    return p.run([mod_from(src, path)], REPO_ROOT)


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------- hostsync

def test_hostsync_true_positives():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def f(g):\n"
        "    x = jnp.sum(g)\n"
        "    a = float(x)\n"             # sync-scalar-cast
        "    b = x.item()\n"             # sync-item
        "    c = np.asarray(x)\n"        # sync-asarray
        "    d = jax.device_get(x)\n"    # sync-device-get
        "    x.block_until_ready()\n"    # sync-block-until-ready
        "    return a, b, c, d\n")
    assert rules_of(run_pass(hostsync, src)) == [
        "sync-asarray", "sync-block-until-ready", "sync-device-get",
        "sync-item", "sync-scalar-cast"]


def test_hostsync_true_negatives():
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from ..obs.timers import fence, fenced_get\n"
        "def f(g, host_rows):\n"
        "    x = jnp.sum(g)\n"
        "    n = int(x.shape[0])\n"      # shape metadata: never a sync
        "    h = fenced_get(x)\n"        # the sanctioned counted readback
        "    fence(x)\n"                 # counted sync, not flagged
        "    y = np.asarray(host_rows)\n"  # unprovable receiver: silent
        "    z = float(n)\n"             # host int, not a device value
        "    return h, y, z\n")
    assert run_pass(hostsync, src) == []


def test_hostsync_flow_sensitive():
    # the host->device rebind pattern from ops/predict.py: np.asarray on
    # a name that only LATER becomes a device value must not fire
    src = (
        "import jax\n"
        "import numpy as np\n"
        "def f(V):\n"
        "    V = np.concatenate([np.asarray(V), np.zeros(4)])\n"
        "    V = jax.device_put(V)\n"
        "    return V\n")
    assert run_pass(hostsync, src) == []


def test_hostsync_out_of_scope_module_silent():
    src = "import jax\nx = jax.device_get(1)\n"
    assert run_pass(hostsync, src, path="lightgbm_tpu/io/fixture.py") == []


# --------------------------------------------------------------- recompile

def test_recompile_true_positives():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnames=('nope',))\n"   # drift
        "def f(x, k):\n"
        "    return x * k\n"
        "@partial(jax.jit, static_argnames=('cfg',))\n"
        "def g(x, cfg):\n"
        "    return x\n"
        "def loop(xs):\n"
        "    out = []\n"
        "    for x in xs:\n"
        "        fn = jax.jit(lambda v: v + 1)\n"          # jit-in-loop
        "        out.append(fn(x))\n"
        "        out.append(g(x, cfg={'a': 1}))\n"         # unhashable
        "    return out\n")
    assert rules_of(run_pass(recompile, src)) == [
        "jit-in-loop", "jit-static-drift", "jit-unhashable-static"]


def test_recompile_true_negatives():
    src = (
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnames=('k',))\n"
        "def f(x, k):\n"
        "    return x * k\n"
        "def factory():\n"
        "    return jax.jit(lambda v: v + 1)\n"
        "def loop(xs):\n"
        "    return [f(x, k=2) for x in xs]\n")
    assert run_pass(recompile, src) == []


# ------------------------------------------------------------ event schema

def test_events_true_positives():
    src = (
        "def emit_stuff(obs, t):\n"
        "    obs.event('no_such_event_xyz', t=t)\n"        # unknown type
        "    obs.event('iter', t=t, bogus_field=1)\n"      # unknown field
        "    obs.event('straggler', t=t)\n")               # missing req'd
    rules = rules_of(run_pass(
        events_schema, src, path="lightgbm_tpu/obs/fixture.py"))
    assert "event-unknown-type" in rules
    assert "event-unknown-field" in rules
    assert "event-missing-field" in rules


def test_events_true_negatives():
    from lightgbm_tpu.obs import events as ev
    req = sorted(ev._REQUIRED["iter"])
    kw = ", ".join("%s=1" % k for k in req)
    src = (
        "def emit_stuff(obs, t, extra):\n"
        "    obs.event('iter', %s)\n"                      # exact schema
        "    obs.event('iter', **extra)\n"                 # splat: trusted
        "    q = []\n"
        "    q.append(('not_an_event_name', {'free': 1}))\n" % kw)
    assert run_pass(events_schema, src,
                    path="lightgbm_tpu/obs/fixture.py") == []


def test_events_schema_tables_cover_repo():
    # the repo's own emit sites all pass the schema pass (no drift
    # between obs/events.py declarations and real call sites)
    mods = core.load_modules(REPO_ROOT)
    assert events_schema.run(mods, REPO_ROOT) == []


# ----------------------------------------------------------------- config

def test_config_true_positives():
    src = (
        "def f(config):\n"
        "    a = config.definitely_not_a_param_xyz\n"      # unknown read
        "    b = config.raw.get('definitely_not_a_key_xyz')\n"
        "    return a, b\n")
    assert rules_of(run_pass(config_coherence, src)) == [
        "config-unknown-key", "config-unknown-read"]


def test_config_true_negatives():
    src = (
        "import jax\n"
        "def f(config):\n"
        "    jax.config.update('jax_enable_x64', True)\n"  # foreign config
        "    a = config.num_leaves\n"
        "    b = config.raw.get('max_bin', 255)\n"
        "    c = config.raw.get('two_round', 'false')\n"   # alias is fine
        "    return a, b, c\n")
    assert run_pass(config_coherence, src) == []


def test_config_registry_and_doc_fresh():
    # registry internally consistent and docs/Parameters.md regenerates
    # byte-identical (the CI regen-diff gate)
    findings = config_coherence.run([], REPO_ROOT)
    assert findings == []


# ------------------------------------------------------------------- vmem

def test_vmem_clean_on_repo_planners():
    # PR-11 invariants hold: every cell the VMEM gate admits plans a live
    # set within physical VMEM, no serialized chunked-RMW plan, and the
    # hist kernel fits its tile budget at every width it claims
    assert vmem.run(core.load_modules(REPO_ROOT), REPO_ROOT) == []


def test_vmem_detects_planner_regression(monkeypatch):
    # resurrect the pathology: a report that claims an over-VMEM live
    # set and a serialized plan must produce both findings
    from lightgbm_tpu.ops import pallas_wave

    def bad_report(n, fc, bp, w, **kw):
        return {"live_new": 300 << 20, "pathological_new": True,
                "resident_bytes": 60 << 20}
    monkeypatch.setattr(pallas_wave, "tile_plan_vmem_report", bad_report)
    rules = set(rules_of(vmem.run([], REPO_ROOT)))
    assert "vmem-budget" in rules
    assert "vmem-serialized-rmw" in rules


def test_vmem_detects_hist_tile_regression(monkeypatch):
    # the original bug: tile_shape hands back a chunk whose one-hot
    # blows the budget for a bin width supports_bins() claims
    from lightgbm_tpu.ops import pallas_hist
    monkeypatch.setattr(pallas_hist, "tile_shape", lambda b: (8, 4096))
    assert "vmem-hist-tile" in rules_of(vmem.run([], REPO_ROOT))


# ------------------------------------------- suppressions and baselines

def test_inline_suppression_honored():
    src = (
        "import jax\n"
        "def f(x):\n"
        "    return jax.device_get(x)  "
        "# lint: ignore[sync-device-get] fixture\n")
    mods = [mod_from(src)]
    raw = hostsync.run(mods, REPO_ROOT)
    assert rules_of(raw) == ["sync-device-get"]
    assert core.apply_suppressions(raw, mods) == []


def test_suppression_star_and_wrong_rule():
    src_star = ("import jax\n"
                "def f(x):\n"
                "    return jax.device_get(x)  # lint: ignore[*]\n")
    mods = [mod_from(src_star)]
    assert core.apply_suppressions(hostsync.run(mods, REPO_ROOT),
                                   mods) == []
    src_wrong = ("import jax\n"
                 "def f(x):\n"
                 "    return jax.device_get(x)  # lint: ignore[sync-item]\n")
    mods = [mod_from(src_wrong)]
    assert rules_of(core.apply_suppressions(
        hostsync.run(mods, REPO_ROOT), mods)) == ["sync-device-get"]


def test_suppression_inside_string_is_inert():
    src = ('MSG = "# lint: ignore[sync-device-get]"\n'
           "import jax\n"
           "def f(x):\n"
           "    return jax.device_get(x)\n")
    mods = [mod_from(src)]
    assert rules_of(core.apply_suppressions(
        hostsync.run(mods, REPO_ROOT), mods)) == ["sync-device-get"]


def test_baseline_round_trip(tmp_path):
    f1 = core.Finding("sync-item", "hostsync",
                      "lightgbm_tpu/ops/x.py", 12, "m")
    f2 = core.Finding("sync-item", "hostsync",
                      "lightgbm_tpu/ops/x.py", 40, "m")
    path = str(tmp_path / "lint_baseline.json")
    core.write_baseline(path, [f1])
    entries = core.load_baseline(path)
    assert core.apply_baseline([f1, f2], entries) == [f2]
    # missing baseline file is an empty grandfather list, not an error
    assert core.load_baseline(str(tmp_path / "nope.json")) == []


def test_corrupt_baseline_fails_closed(tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        f.write("{not json")
    with pytest.raises(core.LintInternalError):
        core.load_baseline(path)
    assert lint_main(["--baseline", path]) == 2     # CLI surfaces exit 2


# ------------------------------------------------------------------- CLI

def test_cli_repo_is_clean():
    # THE acceptance gate: zero unsuppressed findings on this tree
    assert lint_main(["--check"]) == 0


def test_cli_exit_one_on_findings(tmp_path, monkeypatch, capsys):
    fake = tmp_path / "repo" / "lightgbm_tpu" / "ops"
    fake.mkdir(parents=True)
    (fake / "bad.py").write_text(
        "import jax\n"
        "def f(x):\n"
        "    return jax.device_get(x)\n")
    (fake.parent / "__init__.py").write_text("")
    (fake / "__init__.py").write_text("")
    from lightgbm_tpu.analysis import cli as lint_cli
    monkeypatch.setattr(lint_cli, "_repo_root",
                        lambda: str(tmp_path / "repo"))
    assert lint_cli.main(["--check"]) == 1
    out = capsys.readouterr().out
    assert "sync-device-get" in out and "FAIL" in out
    # --json emits machine-readable findings with the full shape
    assert lint_cli.main(["--json"]) == 1
    data = json.loads(capsys.readouterr().out)
    assert data["findings"][0]["rule"] == "sync-device-get"
    assert data["findings"][0]["file"] == "lightgbm_tpu/ops/bad.py"
    # a baseline grandfathering the finding turns the gate green
    bl = str(tmp_path / "bl.json")
    assert lint_cli.main(["--write-baseline", bl]) == 0
    assert lint_cli.main(["--check", "--baseline", bl]) == 0


def test_cli_rules_catalog():
    # every pass contributes at least one rule and ids are unique
    cat = core.rule_catalog()
    assert {p for (p, _) in cat.values()} == {
        "hostsync", "recompile", "events", "config", "vmem"}
    assert lint_main(["--rules"]) == 0


def test_cli_module_entry():
    # `python -m lightgbm_tpu lint --check` — the exact CI spelling
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "lint", "--check"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "lint: clean" in r.stdout


# ----------------------- regression tests for the surfaced true positives

def test_pallas_hist_tile_budget_all_supported_widths():
    # TP #1: the 512 row-chunk floor oversubscribed the ~6 MB tile
    # budget at B>=1024; the floor is now the TPU lane minimum (128)
    # and tile_shape must fit the budget at EVERY width it claims
    from lightgbm_tpu.ops import pallas_hist as ph
    for num_bins in (16, 63, 64, 255, 256, 1023):
        if not ph.supports_bins(num_bins):
            continue
        f_blk, row_chunk = ph.tile_shape(num_bins)
        assert row_chunk >= ph._MIN_ROW_CHUNK
        assert row_chunk % 128 == 0
        resident = f_blk * num_bins * 3 * 4
        onehot = f_blk * num_bins * row_chunk * 4
        assert resident + onehot <= ph.TILE_BUDGET, num_bins
    # the widths that CANNOT fit are refused, not silently oversized
    assert not ph.supports_bins(4096)


def test_pallas_hist_unsupported_width_falls_back():
    # beyond capacity the kernel must hand off to the onehot path with
    # identical results instead of planning an over-budget tile
    from lightgbm_tpu.ops import pallas_hist as ph
    from lightgbm_tpu.ops.histogram import leaf_histogram_onehot
    rng = np.random.RandomState(0)
    nb = 4096
    binned = rng.randint(0, nb, size=(64, 3)).astype(np.int32)
    grad = rng.randn(64).astype(np.float32)
    hess = rng.rand(64).astype(np.float32)
    leaf_id = np.zeros(64, np.int32)
    got = np.asarray(ph.leaf_histogram_pallas(
        binned, grad, hess, leaf_id, 0, None, num_bins=nb))
    want = np.asarray(leaf_histogram_onehot(
        binned, grad, hess, leaf_id, 0, None, num_bins=nb))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fenced_get_counts_and_returns():
    # TP #2/#3: hot-path readbacks now go through the counted twin of
    # fence() so the bench.py --dry sync audit sees them
    import jax.numpy as jnp
    from lightgbm_tpu.obs import timers
    x = jnp.arange(4)
    c0 = timers.fence_count()
    out = timers.fenced_get(x)
    assert timers.fence_count() == c0 + 1
    np.testing.assert_array_equal(np.asarray(out), np.arange(4))
    # non-jax values pass through (device_get is identity-ish on host)
    assert timers.fenced_get({"a": 3})["a"] == 3


def test_materialize_readback_is_audited():
    # training then materializing a tree must bump the sync audit —
    # previously these device_get calls were invisible to fence_count()
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import timers
    rng = np.random.RandomState(7)
    X = rng.rand(200, 4)
    y = (X[:, 0] + rng.rand(200) > 1.0).astype(np.float64)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "max_bin": 31, "verbose": -1},
                      train_set=lgb.Dataset(X, label=y))
    bst.update()
    c0 = timers.fence_count()
    bst.model_to_string()           # forces batched materialization
    assert timers.fence_count() > c0
