"""The span ring, the step program's scopes and the grow loop's counters
(obs/timers.py; docs/Observability.md "Spans, scopes and counters").

The ring is process-global and the driver runs this file's tests in one
worker: every test clears it first.
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import timers


@pytest.fixture(autouse=True)
def _empty_ring():
    timers.clear()
    yield
    timers.clear()


def _spans(name=None):
    return [r for r in timers.snapshot()
            if r["kind"] == "span" and name in (None, r["name"])]


def _xy(n, f, seed):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.1 * rng.standard_normal(n) > 0)
    return X, y.astype(np.float32)


WAVE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
        "verbose": -1, "tpu_growth": "wave", "tpu_histogram_mode": "pallas_t",
        "tpu_pallas_interpret": True, "tpu_wave_width": 4}


# ------------------------------------------------------------------ the ring

def test_span_records_nesting_cause_and_sequence():
    with timers.span("outer") as outer:
        with timers.span("inner") as inner:
            pass
        with timers.span("sibling"):
            pass
    recs = _spans()
    assert [r["name"] for r in recs] == ["inner", "sibling", "outer"]
    by = {r["name"]: r for r in recs}
    assert by["outer"]["cause"] is None
    assert by["inner"]["cause"] == by["sibling"]["cause"] == outer["seq"]
    assert outer["seq"] < inner["seq"] < by["sibling"]["seq"]
    for r in recs:
        assert r["t0"] <= r["t1"]
    assert by["outer"]["t0"] <= by["inner"]["t0"]
    assert by["sibling"]["t1"] <= by["outer"]["t1"]


def test_spans_of_one_iteration_share_it():
    with timers.span("iteration", it=7):
        with timers.span("dispatch"):
            with timers.span("upload_shard", shard=2):
                pass
    by = {r["name"]: r["ids"] for r in _spans()}
    assert by["iteration"] == {"it": 7} and by["dispatch"] == {"it": 7}
    assert by["upload_shard"] == {"it": 7, "shard": 2}


def test_self_seconds_is_duration_less_what_children_cover():
    ms = 1_000_000
    recs = [
        {"kind": "span", "name": "p", "seq": 1, "cause": None,
         "t0": 0, "t1": 100 * ms, "ids": {}},
        {"kind": "span", "name": "a", "seq": 2, "cause": 1,
         "t0": 10 * ms, "t1": 40 * ms, "ids": {}},
        # overlaps `a` for 10 ms: the union is counted, not the sum
        {"kind": "span", "name": "b", "seq": 3, "cause": 1,
         "t0": 30 * ms, "t1": 60 * ms, "ids": {}},
        {"kind": "span", "name": "grandchild", "seq": 4, "cause": 2,
         "t0": 15 * ms, "t1": 20 * ms, "ids": {}},
        {"kind": "count", "name": "tree", "seq": 5, "cause": 1,
         "t": 50 * ms, "fields": {}},
    ]
    own = timers.self_seconds(recs)
    assert own[1] == pytest.approx(0.050)
    assert own[2] == pytest.approx(0.025)
    assert own[3] == pytest.approx(0.030)
    assert own[4] == pytest.approx(0.005)
    assert 5 not in own


def test_count_record_names_the_open_span():
    timers.count("tree", it=0, waves=3)
    with timers.span("materialize") as rec:
        timers.count("tree", it=1, waves=4)
    counts = [r for r in timers.snapshot() if r["kind"] == "count"]
    assert [c["cause"] for c in counts] == [None, rec["seq"]]
    assert counts[1]["fields"] == {"it": 1, "waves": 4}


def test_ring_is_bounded_and_keeps_the_newest():
    for i in range(timers.RING_SIZE + 50):
        timers.count("n", i=i)
    recs = timers.snapshot()
    assert len(recs) == timers.RING_SIZE
    assert recs[-1]["fields"]["i"] == timers.RING_SIZE + 49
    assert recs[0]["fields"]["i"] == 50


def test_snapshot_is_a_copy_and_clear_empties():
    with timers.span("x"):
        pass
    snap = timers.snapshot()
    snap[0]["name"] = "changed"
    assert timers.snapshot()[0]["name"] == "x"
    timers.clear()
    assert timers.snapshot() == []


def test_jax_durations_become_children_only_inside_a_span():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def outside(x):
        return x * 3 + 1

    outside(jnp.ones(7)).block_until_ready()
    assert _spans() == []               # nobody's span was open

    @jax.jit
    def inside(x):
        return jnp.sin(x) * 5

    x = jnp.ones(11)
    with timers.span("iteration", it=0) as it:
        inside(x).block_until_ready()
    kids = [r for r in _spans() if r["cause"] == it["seq"]]
    assert {"trace", "lower", "compile"} <= {r["name"] for r in kids}
    for r in kids:
        assert r["ids"]["it"] == 0 and "inside" in r["ids"]["entry"]
        assert it["t0"] <= r["t1"] <= it["t1"] and r["t0"] <= r["t1"]


def test_outer_duration_adopts_the_inner_and_replaces_its_own_kind():
    """JAX reports the inner interval first: a jit traced inside the step's
    trace, the cache load inside the compile that asked for it."""
    trace = "/jax/core/compile/jaxpr_trace_duration"
    with timers.span("dispatch") as parent:
        timers._on_jax_duration(trace, 0.001, fun_name="inner_jit")
        timers._on_jax_duration(trace, 0.050, fun_name="step")
        timers._on_jax_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.002)
        timers._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 0.004,
            fun_name="step")
        timers._on_jax_duration("/jax/some/other_event", 1.0)
    by = {}
    for r in _spans():
        by.setdefault(r["name"], []).append(r)
    assert set(by) == {"trace", "cache_load", "compile", "dispatch"}
    assert [r["ids"]["entry"] for r in by["trace"]] == ["step"]
    compile_, load = by["compile"][0], by["cache_load"][0]
    assert load["cause"] == compile_["seq"]
    assert compile_["cause"] == by["trace"][0]["cause"] == parent["seq"]
    own = timers.self_seconds(_spans())
    assert own[compile_["seq"]] == pytest.approx(0.002, abs=1e-6)


def test_span_enters_a_profiler_annotation(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    with timers.span("hooked"):     # the real one is resolved by now
        pass
    timers.clear()
    monkeypatch.setattr(timers, "_annotation", Annotation)
    with timers.span("iteration", it=4) as rec:
        pass
    assert seen == [("lgbm_iteration", {"seq": rec["seq"], "it": 4})]


# ------------------------------------------------- spans of a training run

@pytest.fixture(scope="module")
def binned_dir(tmp_path_factory):
    X, y = _xy(3000, 10, 0)
    path = str(tmp_path_factory.mktemp("binned") / "d")
    from lightgbm_tpu.io import binned_format
    ds = lgb.Dataset(X, label=y, params=dict(WAVE)).construct()
    binned_format.save_training_data(ds._handle, path, shard_rows=1024)
    return path


def _train_binned(path, rounds=3, **extra):
    params = dict(WAVE, tpu_fused_iter="on", **extra)
    ds = lgb.Dataset.from_binned(path, params=params)
    ds.construct()
    bst = lgb.Booster(params, ds)
    for _ in range(rounds):
        bst.update()
    return bst


def test_from_binned_run_records_setup_iteration_and_materialize(binned_dir):
    bst = _train_binned(binned_dir)
    bst._gbdt._materialize()
    names = [r["name"] for r in _spans()]
    for name in ("dataset_open", "booster_init", "learner_build", "upload",
                 "upload_shard", "host_copy", "h2d", "iteration", "dispatch",
                 "materialize"):
        assert name in names, name
    by_seq = {r["seq"]: r for r in _spans()}
    shards = _spans("upload_shard")
    assert [r["ids"]["shard"] for r in shards] == list(range(len(shards)))
    for r in shards:
        assert by_seq[r["cause"]]["name"] == "upload"
    for r in _spans("host_copy") + _spans("h2d"):
        assert by_seq[r["cause"]]["name"] == "upload_shard"
    assert by_seq[_spans("upload")[0]["cause"]]["name"] == "learner_build"
    its = _spans("iteration")
    assert [r["ids"]["it"] for r in its] == [0, 1, 2]
    for it, d in zip(its, _spans("dispatch")):
        assert d["cause"] == it["seq"] and d["ids"]["it"] == it["ids"]["it"]
    # the first dispatch traced, lowered and compiled the step; later ones
    # only dispatch
    first = [r["name"] for r in _spans()
             if r["ids"].get("it") == 0 and r["ids"].get("entry")]
    assert {"trace", "lower", "compile"} <= set(first)
    assert not [r for r in _spans() if r["ids"].get("it") in (1, 2)
                and r["name"] in ("trace", "lower", "compile")]


def test_ring_outlives_the_booster(binned_dir):
    import gc
    bst = _train_binned(binned_dir, rounds=2)
    bst._gbdt._materialize()
    del bst
    gc.collect()
    assert len(_spans("iteration")) == 2
    assert len([r for r in timers.snapshot() if r["kind"] == "count"]) == 2


@pytest.mark.parametrize("fused", ["on", "off"])
def test_default_train_issues_no_new_fence(fused):
    """20 rounds: the stop check reads ``num_leaves`` at iterations 0 and
    16, reading the model materializes once.  The ring, the counters and
    the scope table add nothing to that."""
    X, y = _xy(400, 6, 3)
    params = dict(WAVE, num_leaves=7, tpu_fused_iter=fused)
    before = timers.fence_count()
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=20)
    assert timers.fence_count() - before == 2
    bst.model_to_string()
    assert timers.fence_count() - before == 3
    assert len(_spans("iteration")) == 20


def test_staged_chain_records_one_dispatch_a_tree():
    X, y = _xy(400, 6, 3)
    params = dict(WAVE, num_leaves=7, tpu_fused_iter="off")
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=3)
    its = _spans("iteration")
    assert len(its) == 3
    for it in its:
        assert len([d for d in _spans("dispatch")
                    if d["cause"] == it["seq"]]) == 1


def test_fused_branch_laps_one_step(tmp_path):
    """One dispatch, one lap: `step`, where the fused program runs; the
    staged chain keeps grow / partition."""
    phases = {}
    for fused in ("on", "off"):
        X, y = _xy(400, 6, 3)
        path = str(tmp_path / ("events_%s.jsonl" % fused))
        params = dict(WAVE, num_leaves=7, tpu_fused_iter=fused,
                      obs_events_path=path, obs_timing="phase")
        bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                        num_boost_round=3)
        bst.finalize_telemetry()
        with open(path) as f:
            events = [json.loads(line) for line in f if line.strip()]
        iters = [e for e in events if e.get("ev") == "iter"]
        phases[fused] = set().union(*(e["phases"] for e in iters))
    assert "step" in phases["on"]
    assert not {"grow", "partition"} & phases["on"]
    assert {"grow", "partition"} <= phases["off"]
    assert "step" not in phases["off"]


def test_trace_window_writes_spans_beside_the_xplane(tmp_path, monkeypatch):
    from lightgbm_tpu.obs import profile

    monkeypatch.setattr(profile, "_start_trace", lambda d: None)
    monkeypatch.setattr(profile, "_stop_trace", lambda: None)

    class Obs:
        def event(self, *a, **kw):
            pass

    timers.register_device_scopes(
        'HloModule jit_probe, is_scheduled=true\n\nENTRY %main () -> f32[] {\n'
        '  %fusion.1 = f32[] fusion(), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(probe)/split_search/add"}\n}\n')
    window = profile.TraceWindow("0:1", str(tmp_path / "trace"))
    window.maybe_start(0, Obs())
    with timers.span("iteration", it=0):
        timers.count("tree", it=0, waves=1)
    window.maybe_stop(0, Obs())
    with open(os.path.join(str(tmp_path / "trace"), profile.SPANS_FILE)) as f:
        written = json.load(f)
    assert [r["name"] for r in written["records"]] == ["tree", "iteration"]
    assert written["scopes"]["jit_probe"] == {"fusion.1": "split_search"}
    assert written["declared_scopes"] == list(timers.SCOPES)


# ------------------------------------------------- scopes and their table

HLO = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.3 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(step)/jit(grow)/while/body/tree_commit/add"}
}

%body (arg: (f32[8])) -> (f32[8]) {
  %arg = (f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%arg), index=0
  %fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused_computation.1
  %wave_histogram_pallas_t.12 = f32[8]{0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(grow)/while/body/wave_histogram/jit(wave_histogram_pallas_t)/pallas_call" stack_frame_id=3}
  %fusion.9 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%other, metadata={op_name="jit(step)/jit(grow)/while/body/split_search/vmap()/mul"}
  ROOT %tuple = (f32[8]{0}) tuple(%fusion.9)
}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %select_add_fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%x, metadata={op_name="jit(step)/jit(grow)/root_histogram/jit(leaf_histogram_onehot)/add"}
  %copy.1 = f32[8]{0} copy(%p)
  ROOT %fusion.30 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%y, metadata={op_name="jit(step)/score_update/wave_histogram/add"}
}
'''


def test_scope_table_reads_instruction_names_from_hlo_text():
    module, table = timers.scope_table(HLO)
    assert module == "jit_step"
    assert table["wave_histogram_pallas_t.12"] == "wave_histogram"
    assert table["fusion.9"] == "split_search"
    assert table["select_add_fusion.2"] == "root_histogram"
    # the first declared scope of the op_name wins
    assert table["fusion.30"] == "score_update"
    # a fusion with no metadata of its own takes its body's scope
    assert table["fusion.7"] == "tree_commit"
    assert "copy.1" not in table and "gte" not in table


def test_device_time_by_scope_folds_pairs_and_counts_the_rest_unscoped():
    _, table = timers.scope_table(HLO)
    ops = [["wave_histogram_pallas_t.12", 15.0], ["fusion.9", 1.5],
           ["fusion.7", 0.25], ["copy.1", 0.125], ["fusion.9999", 0.125],
           ["select_add_fusion.2", 0.5]]
    assert timers.device_time_by_scope(ops, table) == {
        "wave_histogram": 15.0, "split_search": 1.5, "tree_commit": 0.25,
        "root_histogram": 0.5, timers.UNSCOPED: 0.25}
    assert timers.device_time_by_scope([], table) == {}
    assert timers.device_time_by_scope(ops[:1], {}) == {timers.UNSCOPED: 15.0}


def test_register_keeps_one_table_a_module():
    assert timers.register_device_scopes(HLO) == "jit_step"
    assert timers.register_device_scopes("no module here") is None
    tables = timers.device_scopes()
    assert tables["jit_step"]["fusion.9"] == "split_search"
    tables["jit_step"].clear()          # a copy: the module's own stands
    assert timers.device_scopes()["jit_step"]


def test_fused_step_registers_every_scope_it_runs(binned_dir):
    """The tiny fused step compiled here: its own optimised HLO names
    every phase of the serial wave grower (`hist_allreduce` runs only
    under a mesh)."""
    timers._scopes.clear()
    _train_binned(binned_dir, rounds=1)
    tables = timers.device_scopes()
    assert list(tables) == ["jit_step"]
    found = set(tables["jit_step"].values())
    assert found == set(timers.SCOPES) - {"hist_allreduce", "bundle_view"}
    # no bundle in the dataset: no scope, no counter, no span of it
    assert not [r for r in timers.snapshot()
                if r["name"] in ("bundle", "bundle_layout")]


@pytest.fixture(scope="module")
def bundled_dir(tmp_path_factory):
    """Twelve one-hot columns of one source column and two dense ones:
    EFB packs the twelve into one group."""
    rng = np.random.default_rng(1)
    level = rng.integers(0, 12, 3000)
    X = np.concatenate([np.eye(12)[level], rng.standard_normal((3000, 2))],
                       axis=1).astype(np.float32)
    y = ((level % 3 == 0) + 0.3 * X[:, 12]
         + 0.1 * rng.standard_normal(3000) > 0.5).astype(np.float32)
    path = str(tmp_path_factory.mktemp("bundled") / "d")
    from lightgbm_tpu.io import binned_format
    ds = lgb.Dataset(X, label=y, params=dict(WAVE)).construct()
    assert ds._handle.bundle is not None
    binned_format.save_training_data(ds._handle, path, shard_rows=1024)
    return path


@pytest.mark.parametrize("mode", ["pallas_t", "pallas_ct"])
def test_a_bundled_dataset_has_its_scope_span_and_counters(bundled_dir,
                                                           mode):
    """`bundle_view` (the EFB view in front of every split search) is a
    scope of its own INSIDE `split_search`, under a vmap, and taken out
    of it; `from_binned` rebuilds the layout under a span; one `bundle`
    record a learner says what EFB made of the features."""
    from lightgbm_tpu.ops.plan import store_bin_width, store_col_pads

    timers._scopes.clear()
    timers.clear()
    bst = _train_binned(bundled_dir, rounds=1, tpu_histogram_mode=mode)
    table = timers.device_scopes()["jit_step"]
    # the fused kernel routes and histograms every row: no slab to move
    assert set(table.values()) == set(timers.SCOPES) - {"hist_allreduce"} - (
        {"wave_compact"} if mode == "pallas_ct" else set())
    assert timers._scope_of(
        "jit(step)/jit(grow)/while/body/split_search/split_search/"
        "vmap(bundle_view)/gather") == "bundle_view"
    assert timers._scope_of(
        "jit(step)/jit(grow)/split_search/mul") == "split_search"
    layout = bst._gbdt.train_data.bundle
    (rec,) = [r for r in timers.snapshot()
              if r["kind"] == "count" and r["name"] == "bundle"]
    by_seq = {r["seq"]: r for r in _spans()}
    assert by_seq[rec["cause"]]["name"] == "learner_build"
    bundled = [g for g in layout.groups if len(g) > 1]
    assert bundled and rec["fields"] == {
        "bundle_groups": layout.num_groups,
        "bundled_features": sum(len(g) for g in bundled),
        "group_bins_used": int(layout.num_group_bins.sum()),
        "group_bins_padded": sum(store_col_pads(
            bst._gbdt.learner.plan, layout.num_group_bins,
            int(layout.num_group_bins.max())))
        or layout.num_groups * store_bin_width(
            bst._gbdt.learner.plan, int(layout.num_group_bins.max()))}
    # the interpreted pallas_t pads every group to the widest one's 256;
    # the fused kernel multiplies each against its own bins by granules
    # of 32: the twelve one-hot columns' 13 bins against 32
    assert sorted(layout.num_group_bins) == [13, 255, 255]
    assert rec["fields"]["group_bins_padded"] == (
        layout.num_groups * 256 if mode == "pallas_t" else 32 + 256 + 256)
    (span,) = _spans("bundle_layout")
    assert by_seq[span["cause"]]["name"] == "dataset_open"
    assert span["ids"]["groups"] == layout.num_groups


def test_one_executable_a_signature_and_no_compile_after_the_first(
        binned_dir):
    bst = _train_binned(binned_dir, rounds=3)
    fused = bst._gbdt._fused_state[0]
    assert len(fused._compiled) == 1
    assert len(_spans("compile")) >= 1
    timers.clear()
    bst.update()
    assert not _spans("compile") and not _spans("trace")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


def test_kernel_keeps_a_name_the_benchmark_finds_when_lowered_for_tpu(
        topo, monkeypatch):
    """Compiled for a described v5e, not interpreted: the Mosaic custom
    call is still `%wave_histogram...` (benchmark/trace.py
    HIST_KERNEL_MARKS) and sits under the `wave_histogram` scope."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.ops.pallas_wave import wave_histogram_pallas_t

    one = SingleDeviceSharding(topo.devices[0])

    def wave(xt, lid, w3, cid):
        with jax.named_scope("wave_histogram"):
            return wave_histogram_pallas_t(xt, lid, w3, cid, 63)

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((64, 4096), jnp.uint8), ((4096,), jnp.int32),
        ((4096, 3), jnp.float32), ((8,), jnp.int32))]
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(wave).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
    _, table = timers.scope_table(text)
    kernels = [name for name in table
               if ("%" + name).startswith(("%wave_histogram",
                                           "%wave_partition_hist"))]
    assert kernels, sorted(table)[:20]
    assert {table[k] for k in kernels} == {"wave_histogram"}
    assert 'custom_call_target="tpu_custom_call"' in text


def test_early_stop_kernel_compiles_for_tpu(topo):
    """A slab launch (W=32, single-bf16 products, eight row tiles)
    compiled by Mosaic for a described v5e: scalar prefetch, clamped
    index maps and the skipped body pass the chip's compiler, the launch
    pads nothing, and the call keeps the name the benchmark finds.  (At
    a cell's own 2,000 columns the same compile takes 77 s here: too
    long for this suite.)"""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.ops.pallas_wave import (slab_plan,
                                              wave_histogram_pallas_t)

    assert slab_plan(1_200_128, 2000, 63, 32) == (600_064, 2_048)
    assert slab_plan(2_500_608, 968, 63, 32) == (1_250_944, 3_712)
    cap, tile = slab_plan(131_072, 64, 63, 32)
    assert cap == 65_536 == 8 * tile
    one = SingleDeviceSharding(topo.devices[0])

    def wave(xt, lid, w3, cid, n_active):
        with jax.named_scope("wave_histogram"):
            return wave_histogram_pallas_t(xt, lid, w3, cid, 63, hilo=False,
                                           n_active=n_active)

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((64, cap), jnp.uint8), ((cap,), jnp.int32),
        ((cap, 3), jnp.float32), ((32,), jnp.int32), ((), jnp.int32))]
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(wave).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
    assert "%wave_histogram_pallas_t" in text
    assert " pad(" not in text         # cap is the launch's own tile multiple


def test_grow_program_holds_one_instance_of_the_slab_kernel(monkeypatch):
    """The wave grower as `auto` builds it for a wide table on the chip
    (pallas_t, W=32, the row slab, cached histograms), lowered for the
    TPU: ONE Mosaic custom call, `wave_histogram_pallas_t`, called from
    ONE place.  The move in front of it runs by chunks with a traced trip
    count, and nothing chooses between two launches: a ladder of slab
    sizes (`lax.cond` arms, each with the kernel's 38.5 MB of device code;
    deleted in PR 27) or a second form of the move kept beside the first
    would show here as a second instance.  The record the loop fills
    names what the move gathered."""
    import re
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import wave
    from lightgbm_tpu.ops.learner import build_split_params
    from lightgbm_tpu.ops.split_finder import FeatureMeta
    from lightgbm_tpu.utils.config import Config

    assert "slab_rows_moved" in timers.COUNTERS
    assert timers.COUNTERS.index("slab_rows_moved") \
        == timers.COUNTERS.index("kernel_rows") + 1
    wave.make_wave_core.cache_clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, f = 131_072, 64
    meta = FeatureMeta(num_bin=jnp.full(f, 63, jnp.int32),
                       default_bin=jnp.zeros(f, jnp.int32),
                       is_categorical=jnp.zeros(f, bool))
    grow = wave.make_wave_grow_fn(
        255, 63, meta, build_split_params(Config({"verbose": -1})), -1,
        wave_width=32, hist_mode="pallas_t", with_xt=True)
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((n, f), jnp.uint8), ((n,), jnp.float32), ((n,), jnp.float32),
        ((n,), jnp.float32), ((f,), jnp.bool_), ((f, n), jnp.uint8))]
    try:
        text = jax.jit(grow).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        wave.make_wave_core.cache_clear()
    assert text.count("@tpu_custom_call") == 1
    assert re.findall(r'kernel_name = "(\w+)"', text) \
        == ["wave_histogram_pallas_t"]
    assert len(re.findall(r"call @wave_histogram_pallas_t", text)) == 1


def test_split_search_reads_the_wave_block_once_when_compiled_for_tpu(topo):
    """The search of a wave's 64 children at Epsilon's width, compiled
    for a described v5e: the running sums over the bins on the MXU and
    every pass derived from them, so no reduce-window over the bins, no
    reversal and no gather is left, and XLA's own count of the memory
    traffic is a third of the 17.3 GB that nine cumulative sums, six
    flips and twenty gathers read for the 98 MB block (PR 31; this form
    reads 3.3 GB)."""
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.ops.split_finder import (FeatureMeta, SplitParams,
                                               best_splits_vmapped)

    one = SingleDeviceSharding(topo.devices[0])
    params = SplitParams(0.0, 0.0, 0.0, 1.0, 100.0, True)
    K, F, B = 64, 2000, 64

    def search(hists, sums, depths, num_bin, default_bin, is_cat, mask):
        with jax.named_scope("split_search"):
            return best_splits_vmapped(
                hists, sums, depths,
                FeatureMeta(num_bin, default_bin, is_cat), mask, params, -1)

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((K, F, B, 3), jnp.float32), ((K, 3), jnp.float32),
        ((K,), jnp.int32), ((F,), jnp.int32), ((F,), jnp.int32),
        ((F,), jnp.bool_), ((F,), jnp.bool_))]
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(search).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
    text = compiled.as_text()
    assert " convolution(" in text          # the running sums, on the MXU
    assert not re.findall(r"\b(reduce-window|reverse|gather)\(", text)
    assert compiled.cost_analysis()["bytes accessed"] < 5.8e9


# ---------------------------------------------- the grow loop's counters

def _tree_counts(bst):
    bst._gbdt._materialize()
    return [r["fields"] for r in timers.snapshot()
            if r["kind"] == "count" and r["name"] == "tree"]


def _replay_waves(tree, width):
    """Waves a host replay of the wave order takes: every wave commits the
    next ``min(width, budget)`` internal nodes (node ids are handed out in
    commit order), as long as the frontier holds that many candidates."""
    left = tree.num_leaves - 1
    waves = 0
    node = 0
    while left > 0:
        # nodes of one wave are children of leaves that existed before it
        depth_ok = 0
        for k in range(min(width, left)):
            n = node + k
            parent = _parent_of(tree, n)
            if parent is not None and parent >= node:
                break
            depth_ok += 1
        node += depth_ok
        left -= depth_ok
        waves += 1
    return waves


def _parent_of(tree, node):
    for p in range(tree.num_leaves - 1):
        if tree.left_child[p] == node or tree.right_child[p] == node:
            return p
    return None


@pytest.mark.parametrize("fused", ["on", "off"])
def test_counters_agree_with_the_grown_tree(fused):
    X, y = _xy(3000, 10, 0)
    params = dict(WAVE, tpu_fused_iter=fused)
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=3)
    counts = _tree_counts(bst)
    assert [c["it"] for c in counts] == [0, 1, 2]
    rows = 3000 + (-3000) % 1024
    for c, tree in zip(counts, bst._gbdt.models):
        ni = tree.num_leaves - 1
        assert c["committed"] == ni == c["attempted"]
        kids = lambda ch: (tree.leaf_count[~ch] if ch < 0     # noqa: E731
                           else tree.internal_count[ch])
        assert c["hist_rows"] == sum(
            min(kids(tree.left_child[i]), kids(tree.right_child[i]))
            for i in range(ni))
        assert c["waves"] == _replay_waves(tree, 4)
        assert c["slots"] == 4 * c["waves"]
        assert c["rows"] == rows
        # WAVE runs the pallas_t kernel (interpreted): every wave reads
        # the row slab of its smaller children, one tile of rows / 2
        assert c["compacted"] == c["waves"]
        assert c["kernel_rows"] == c["waves"] * (rows // 2)
        assert c["rows_visited"] == rows + c["kernel_rows"]
        assert c["hist_rows"] < c["rows_visited"]


@pytest.mark.parametrize("engine", [
    {"tpu_pallas_interpret": False},
    {"tpu_histogram_mode": "pallas_ct"}], ids=["xla", "pallas_ct"])
def test_without_the_slab_every_wave_visits_every_row(engine):
    """The XLA engine (no Pallas kernel on the CPU without the
    interpreter) and the fused partition-and-histogram kernel (the
    narrow cell's, interpreted): no slab, so the record's `kernel_rows`
    is one full pass a wave, added on the host."""
    X, y = _xy(3000, 10, 0)
    params = dict(WAVE, **engine)
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=2)
    rows = 3000 + (-3000) % 1024
    for c in _tree_counts(bst):
        assert c["compacted"] == 0 and c["waves"] > 0
        assert c["kernel_rows"] == c["waves"] * rows
        assert c["rows_visited"] == (c["waves"] + 1) * rows


def test_exact_order_attempts_more_than_it_commits():
    X, y = _xy(3000, 10, 0)
    params = dict(WAVE, tpu_wave_width=8, tpu_wave_order="exact")
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=2)
    counts = _tree_counts(bst)
    for c, tree in zip(counts, bst._gbdt.models):
        assert c["committed"] == tree.num_leaves - 1
        assert c["attempted"] > c["committed"]
        assert c["slots"] == 8 * c["waves"]


def test_leaf_wise_grower_returns_zero_counters():
    X, y = _xy(400, 6, 3)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "tpu_growth": "exact"}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=2)
    for c in _tree_counts(bst):
        assert {c[k] for k in timers.COUNTERS} == {0}
        assert c["rows_visited"] == 0
