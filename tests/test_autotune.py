"""Measured kernel autotuner (ops/autotune.py).

* off-mode parity: with tpu_autotune=off (the CPU-CI default) the
  selected cells are bit-identical to the legacy hand-tuned heuristics
  across the benchmark shape buckets — the tuner must be a pure
  superset of today's behaviour.
* measured selection is deterministic under the injectable bench/timer
  hooks (the SloEngine fake-clock pattern), round-trips through the
  on-disk cache (a warm cache performs ZERO probe waves), and a cache
  schema-rev bump invalidates every old entry.
* off-TPU measure mode is a documented no-op falling back to the prior.
* the former 18-30 MB band prior is GONE: its root cause (the wave
  kernels' row-tile planner ignoring the VMEM-resident accumulator
  block) is fixed in ops/pallas_wave.py::_tile_plan, and the
  `tile_plan_vmem_report` regressions here pin the planner to the
  measured cells the band used to bend — including the yahoo W64
  misfire the (18,30) bounds could never encode.
* a cache file written at another CACHE_SCHEMA_REV is dropped whole:
  `load_cache` returns an empty cache, the next measure run re-probes,
  and the rewritten file carries the current rev (no stale-rev entry
  can be re-merged by `store_cache`).
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import autotune
from lightgbm_tpu.ops.autotune import (Cell, Pins, ShapeBucket, decide,
                                       enumerate_cells, measure_cells,
                                       prior_hist_mode, resolve_wave_order,
                                       resolve_wave_width, row_bucket)
from lightgbm_tpu.utils.config import Config


@pytest.fixture(autouse=True)
def _clean_hooks():
    autotune.clear_probe_hooks()
    yield
    autotune.clear_probe_hooks()


def _cfg(num_leaves, **kw):
    kw.setdefault("verbose", -1)
    kw["num_leaves"] = num_leaves
    return Config(kw)


# --------------------------------------------------------------- off parity

# the benchmark shape buckets (tools/BENCH_SUITE.md) and the cells the
# legacy inline heuristics picked for them on TPU; tpu_autotune=off must
# reproduce these exactly (ncols, bin_pad, num_leaves, mode, width).
# The widths are the RAW ladder values: the band-escape bend that used
# to push epsilon to 32 and bosch to 64 is gone — its root cause lives
# in the tile planner now (test_tile_plan_* below), so the band shapes
# run their natural widths.
LEGACY_TABLE = [
    ("flagship", 28, 256, 255, "pallas_t", 32),   # narrow-F
    ("epsilon", 2000, 64, 63, "pallas_t", 16),    # ex-band: W16 stays 16
    ("msltr", 136, 256, 255, "pallas_t", 32),     # 13.4MB block
    ("expo_cat", 40, 64, 31, "pallas_ct", 8),     # 40*64=2560: ct bound
    ("bosch", 968, 64, 255, "pallas_t", 32),      # ex-band: W32 stays 32
    ("bosch_widepad", 968, 256, 255, "onehot", None),  # 95MB > VMEM gate
]


@pytest.mark.parametrize("name,ncols,bin_pad,leaves,mode,width",
                         LEGACY_TABLE)
def test_off_mode_matches_legacy_heuristics(name, ncols, bin_pad, leaves,
                                            mode, width):
    cfg = _cfg(leaves)
    got_mode = prior_hist_mode(cfg, ncols, bin_pad, leaves, None,
                               on_tpu=True)
    assert got_mode == mode, name
    if width is not None:
        w = resolve_wave_width(cfg, leaves, resolve_wave_order(cfg))
        assert w == width, name


def test_off_mode_decide_is_identity():
    """tpu_autotune=off returns the prior cell untouched — no cache
    read, no probes — while still recording the decision."""
    prior = Cell("pallas_t", 32, True)
    d = decide(_cfg(255), ShapeBucket(28, 256, 255, 1 << 20), prior,
               Pins(), eligible=True)
    assert d.cell == prior and d.source == "off" and not d.probes
    evs = [ev for ev, _ in d.events]
    assert evs == ["autotune_decision"]


def test_ineligible_decide_keeps_prior(tmp_path):
    cfg = _cfg(31, tpu_autotune="measure",
               tpu_autotune_cache=str(tmp_path / "c.json"))
    prior = Cell("onehot", 1, True)
    d = decide(cfg, ShapeBucket(28, 256, 31, 4096), prior, Pins(),
               eligible=False)
    assert d.cell == prior and d.source == "ineligible" and not d.probes


# ----------------------------------------------------------- measured path

def _bench(cell, bucket):
    """Deterministic synthetic cost: wider faster, bf16 beats hilo, ct
    pays a tax."""
    s = 1.0 / max(1, cell.wave_width)
    if cell.hist_hilo:
        s += 0.1
    if cell.hist_mode == "pallas_ct":
        s += 0.5
    return s


def test_measure_mode_deterministic_winner(tmp_path):
    autotune.install_probe_hooks(bench=_bench)
    cfg = _cfg(15, tpu_autotune="measure",
               tpu_autotune_cache=str(tmp_path / "c.json"))
    prior = Cell("pallas_t", 8, True)
    d = decide(cfg, ShapeBucket(8, 64, 15, 2048), prior, Pins(),
               eligible=True)
    assert d.source == "measured" and not d.cache_hit
    # bf16 at the prior width wins under the synthetic costs (which are
    # fused-agnostic, so the fused arm ties the prior and loses the tie)
    assert d.cell == Cell("pallas_t", 8, False)
    assert len(d.probes) == 6 and d.margin > 0 and d.overhead_s > 0
    probe_evs = [f for ev, f in d.events if ev == "autotune_probe"]
    assert len(probe_evs) == 6
    assert all(f["s_per_wave"] == _bench(Cell.from_dict(f["cell"]), None)
               for f in probe_evs)


def test_cache_round_trip_skips_probing(tmp_path):
    autotune.install_probe_hooks(bench=_bench)
    cache = str(tmp_path / "c.json")
    cfg = _cfg(15, tpu_autotune="measure", tpu_autotune_cache=cache)
    prior = Cell("pallas_t", 8, True)
    bucket = ShapeBucket(8, 64, 15, 2048)
    d1 = decide(cfg, bucket, prior, Pins(), eligible=True)
    assert d1.source == "measured"
    with open(cache) as f:
        blob = json.load(f)
    assert blob["version"] == autotune.CACHE_SCHEMA_REV
    assert autotune.cache_key(autotune._device_kind(), bucket) \
        in blob["entries"]
    # warm cache: zero probe waves, same winner
    d2 = decide(cfg, bucket, prior, Pins(), eligible=True)
    assert d2.source == "cache" and d2.cache_hit and not d2.probes
    assert d2.cell == d1.cell
    assert [ev for ev, _ in d2.events] == ["autotune_decision"]
    # a different bucket is a different key -> probes again
    d3 = decide(cfg, ShapeBucket(8, 64, 15, 4096), prior, Pins(),
                eligible=True)
    assert d3.source == "measured"


def test_cache_invalidated_by_schema_rev_bump(tmp_path, monkeypatch):
    autotune.install_probe_hooks(bench=_bench)
    cfg = _cfg(15, tpu_autotune="measure",
               tpu_autotune_cache=str(tmp_path / "c.json"))
    prior = Cell("pallas_t", 8, True)
    bucket = ShapeBucket(8, 64, 15, 2048)
    assert decide(cfg, bucket, prior, Pins(),
                  eligible=True).source == "measured"
    assert decide(cfg, bucket, prior, Pins(),
                  eligible=True).source == "cache"
    monkeypatch.setattr(autotune, "CACHE_SCHEMA_REV",
                        autotune.CACHE_SCHEMA_REV + 1)
    d = decide(cfg, bucket, prior, Pins(), eligible=True)
    assert d.source == "measured" and not d.cache_hit


def test_cached_winner_respects_pins(tmp_path):
    """A cache entry tuned without pins must not override a pinned
    dimension on reuse."""
    autotune.install_probe_hooks(bench=_bench)
    cfg = _cfg(15, tpu_autotune="measure",
               tpu_autotune_cache=str(tmp_path / "c.json"))
    bucket = ShapeBucket(8, 64, 15, 2048)
    d1 = decide(cfg, bucket, Cell("pallas_t", 8, True), Pins(),
                eligible=True)
    assert d1.cell.wave_width == 8  # cached winner: W=8 bf16
    # now the same bucket with width pinned at 4: the cached cell's
    # width must be replaced by the prior's
    prior = Cell("pallas_t", 4, True)
    d2 = decide(cfg, bucket, prior, Pins(width=True), eligible=True)
    assert d2.source == "cache" and d2.cell.wave_width == 4


def test_corrupt_cache_is_empty_cache(tmp_path):
    autotune.install_probe_hooks(bench=_bench)
    cache = tmp_path / "c.json"
    cache.write_text("{not json")
    cfg = _cfg(15, tpu_autotune="measure", tpu_autotune_cache=str(cache))
    d = decide(cfg, ShapeBucket(8, 64, 15, 2048),
               Cell("pallas_t", 8, True), Pins(), eligible=True)
    assert d.source == "measured"   # re-probed, did not raise


def test_stale_rev_cache_file_dropped_whole(tmp_path):
    """Satellite regression (v11): a cache file written at an older
    CACHE_SCHEMA_REV is an EMPTY cache — `load_cache` must not return
    its entries, decide() must re-probe, and the rewritten file must
    carry the current rev with the stale entries gone (store_cache
    merges through load_cache, so returning stale entries would
    resurrect them under a fresh version stamp forever)."""
    autotune.install_probe_hooks(bench=_bench)
    cache = tmp_path / "c.json"
    bucket = ShapeBucket(8, 64, 15, 2048)
    stale_key = autotune.cache_key(autotune._device_kind(), bucket)
    # a plausible rev-1 file: pre-`fused` cell dicts under rev-1 keys
    cache.write_text(json.dumps({
        "version": 1,
        "entries": {
            stale_key.replace("|v%d|" % autotune.CACHE_SCHEMA_REV,
                              "|v1|"): {
                "cell": {"hist_mode": "pallas_ct", "wave_width": 64,
                         "hist_hilo": False},
                "s_per_wave": 1e-9, "waves": 3},
        }}))
    assert autotune.load_cache(str(cache)) == {}
    cfg = _cfg(15, tpu_autotune="measure", tpu_autotune_cache=str(cache))
    prior = Cell("pallas_t", 8, True)
    d = decide(cfg, bucket, prior, Pins(), eligible=True)
    assert d.source == "measured" and not d.cache_hit
    with open(cache) as f:
        blob = json.load(f)
    assert blob["version"] == autotune.CACHE_SCHEMA_REV
    assert list(blob["entries"]) == [stale_key]
    assert blob["entries"][stale_key]["cell"] == d.cell.as_dict()
    assert "fused" in blob["entries"][stale_key]["cell"]
    # and the fresh file is an ordinary warm cache
    d2 = decide(cfg, bucket, prior, Pins(), eligible=True)
    assert d2.source == "cache" and d2.cell == d.cell


def test_force_mode_ignores_cache(tmp_path):
    autotune.install_probe_hooks(bench=_bench)
    cache = str(tmp_path / "c.json")
    bucket = ShapeBucket(8, 64, 15, 2048)
    prior = Cell("pallas_t", 8, True)
    decide(_cfg(15, tpu_autotune="measure", tpu_autotune_cache=cache),
           bucket, prior, Pins(), eligible=True)
    d = decide(_cfg(15, tpu_autotune="force", tpu_autotune_cache=cache),
               bucket, prior, Pins(), eligible=True)
    assert d.source == "measured" and d.probes


def test_measure_off_tpu_is_noop(tmp_path):
    """No TPU, no injected hooks: measure mode falls back to the prior
    with zero probes (CPU CI must not pay wave compiles)."""
    cfg = _cfg(15, tpu_autotune="measure",
               tpu_autotune_cache=str(tmp_path / "c.json"))
    prior = Cell("pallas_t", 8, True)
    d = decide(cfg, ShapeBucket(8, 64, 15, 2048), prior, Pins(),
               eligible=True, probe=lambda cell: (lambda: None))
    assert d.cell == prior and d.source == "prior" and not d.probes
    assert not os.path.exists(str(tmp_path / "c.json"))


def test_measure_cells_injectable_timer():
    """With a fake clock the measured s/wave is exact: the timer ticks
    once before and once after the timed loop."""
    ticks = [0.0]

    def timer():
        ticks[0] += 1.0
        return ticks[0]

    autotune.install_probe_hooks(timer=timer)
    cells = [Cell("pallas_t", 8, True),
             Cell("pallas_t", 16, True)]
    events = []
    out = measure_cells(cells, ShapeBucket(8, 64, 15, 2048),
                        lambda cell: (lambda: None), waves=4,
                        events=events)
    assert [(c, s) for c, s in out] == [(cells[0], 0.25),
                                        (cells[1], 0.25)]
    assert len(events) == 2 and all(e[0] == "autotune_probe"
                                    for e in events)


def test_failed_probe_drops_candidate_not_training():
    autotune.install_probe_hooks(force=True)

    def probe(cell):
        if cell.wave_width == 16:
            raise RuntimeError("mosaic says no")
        return lambda: None

    events = []
    out = measure_cells([Cell("pallas_t", 8, True),
                         Cell("pallas_t", 16, True)],
                        ShapeBucket(8, 64, 15, 2048), probe, 1, events)
    assert [c.wave_width for c, _ in out] == [8]


# ------------------------------------------------------------- enumeration

def test_enumerate_cells_respects_pins_and_gates():
    bucket = ShapeBucket(8, 64, 15, 2048)
    prior = Cell("pallas_t", 8, True)
    cells = enumerate_cells(prior, bucket, Pins())
    assert cells[0] == prior and len(cells) <= autotune.MAX_CELLS
    widths = {c.wave_width for c in cells}
    assert {4, 8, 16} <= widths
    # the staged/fused flip (rev 2) is a candidate when unpinned ...
    assert any(c.fused for c in cells)
    # ... and fully pinned (all four dimensions) only the prior survives
    assert enumerate_cells(
        prior, bucket, Pins(True, True, True, True)) == [prior]
    # pinning fused alone removes exactly the fused arm
    assert all(not c.fused
               for c in enumerate_cells(prior, bucket, Pins(fused=True)))
    # non-wave kernels have no neighbours
    assert enumerate_cells(Cell("onehot", 1, True), bucket,
                           Pins()) == [Cell("onehot", 1, True)]
    # VMEM hard gate: a W*2 neighbour whose block exceeds the budget is
    # not enumerated (bosch-wide: W64 at 968x256 would be 190 MB)
    wide = ShapeBucket(968, 256, 255, 1 << 20)
    big = enumerate_cells(Cell("pallas_t", 32, True), wide, Pins())
    assert all(c.wave_width <= 32 for c in big)
    # ct cells are only candidates where ct may run (serial execution)
    no_ct = enumerate_cells(prior, bucket, Pins(), ct_allowed=False)
    assert all(c.hist_mode == "pallas_t" for c in no_ct)


def test_ct_beyond_promotion_bound_is_a_candidate():
    """The 2560 ct bound is a PRIOR, not a hard gate: measure mode
    probes the ct arm on shapes the heuristic would never promote."""
    bucket = ShapeBucket(136, 256, 255, 1 << 20)   # 34816 >> 2560
    cells = enumerate_cells(Cell("pallas_t", 32, True), bucket,
                            Pins())
    assert any(c.hist_mode == "pallas_ct" for c in cells)


def test_row_bucket_powers_of_two():
    assert row_bucket(1) == 1
    assert row_bucket(1000) == 1024
    assert row_bucket(1024) == 1024
    assert row_bucket(1025) == 2048


# -------------------------------------------------- band prior post-mortem

# The 18-30 MB HIST_BLOCK_BAND and its band_adjusted_width escape were
# deleted: the degeneracy was never a property of the block SIZE but of
# the row-tile planner sizing transients against a fixed 16 MB budget
# that ignored the VMEM-resident accumulator, so mid-size blocks
# oversubscribed Mosaic's ~52 MB overlap window (while huge blocks were
# rescued by the chunked-RMW schedule at ~44 MB resident).  The fix
# lives in ops/pallas_wave.py::_tile_plan; tile_plan_vmem_report is the
# minimal reproduction and these tests keep it fixed.

def test_band_prior_is_gone():
    assert not hasattr(autotune, "HIST_BLOCK_BAND")
    assert not hasattr(autotune, "band_adjusted_width")


def test_tile_plan_fixes_the_ex_band_cells():
    """epsilon W16 and bosch W32 — the two measured in-band cells the
    escape used to bend to wider widths — are pathological under the
    legacy plan and schedulable under the accumulator-aware one."""
    from lightgbm_tpu.ops.pallas_wave import tile_plan_vmem_report
    for fc, bp, k in [(2000, 64, 16), (968, 64, 32)]:
        rep = tile_plan_vmem_report(1 << 20, fc, bp, k)
        assert rep["pathological_old"], (fc, k)
        assert not rep["pathological_new"], (fc, k)
        assert rep["c_new"] < rep["c_old"]
        assert rep["live_new"] <= rep["overlap_window"]


def test_tile_plan_catches_the_band_misfire():
    """yahoo-shaped W64 (700 cols, 64-pad): 32.8 MB resident sits OVER
    the old band's 30 MB upper edge, so the escape declared it clear —
    yet resident + 36 MB of transients blows the overlap window and the
    cell measured 3.2x slow.  The live-set bound flags and fixes it;
    the (18,30) size band never could."""
    from lightgbm_tpu.ops.pallas_wave import tile_plan_vmem_report
    rep = tile_plan_vmem_report(1 << 20, 700, 64, 64)
    assert rep["resident_bytes"] > 30 << 20     # outside the old band
    assert not rep["chunked_rmw"]               # below the chunked rescue
    assert rep["pathological_old"]
    assert not rep["pathological_new"]


def test_tile_plan_leaves_healthy_cells_alone():
    """Shapes that were never degenerate keep their full row tile: the
    flagship (tiny resident block) and bosch W64 (45 MB resident, the
    chunked-RMW schedule overlaps regardless of live set)."""
    from lightgbm_tpu.ops.pallas_wave import tile_plan_vmem_report
    flag = tile_plan_vmem_report(1 << 20, 28, 256, 32)
    assert flag["c_new"] == flag["c_old"] == 8192
    assert not flag["pathological_old"]
    bosch64 = tile_plan_vmem_report(1 << 20, 968, 64, 64)
    assert bosch64["chunked_rmw"]
    assert bosch64["c_new"] == bosch64["c_old"]
    assert not bosch64["pathological_new"]


# ------------------------------------------------------------ integration

def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_train_measure_then_cache_hit(tmp_path):
    """End-to-end through lgb.train on CPU via the bench hook: first
    run probes and persists, second run is a cache hit with zero probe
    waves — the decision/probe events land on the timeline through the
    learner's pending-events queue."""
    rng = np.random.default_rng(3)
    X = rng.standard_normal((800, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    autotune.install_probe_hooks(bench=_bench)

    def run(tag):
        ev_path = str(tmp_path / ("%s.jsonl" % tag))
        p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
             "min_data_in_leaf": 5, "tpu_growth": "wave",
             "tpu_histogram_mode": "pallas_t", "tpu_autotune": "measure",
             "tpu_autotune_cache": str(tmp_path / "cache.json"),
             "obs_events_path": ev_path}
        lgb.train(p, lgb.Dataset(X, label=y, params=p),
                  num_boost_round=2)
        evs = _events(ev_path)
        return ([e for e in evs if e.get("ev") == "autotune_decision"],
                [e for e in evs if e.get("ev") == "autotune_probe"])

    d1, p1 = run("run1")
    assert len(d1) == 1 and d1[0]["source"] == "measured" and p1
    d2, p2 = run("run2")
    assert len(d2) == 1 and d2[0]["source"] == "cache" and not p2
    assert d2[0]["cache_hit"] and d2[0]["cell"] == d1[0]["cell"]


def test_train_off_mode_single_decision(tmp_path):
    """Default params: exactly one decision event, mode off, zero
    probes — the bench.py --dry contract."""
    rng = np.random.default_rng(4)
    X = rng.standard_normal((500, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    ev_path = str(tmp_path / "off.jsonl")
    p = {"objective": "binary", "num_leaves": 7, "verbose": -1,
         "min_data_in_leaf": 5, "obs_events_path": ev_path}
    lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=2)
    evs = _events(ev_path)
    decs = [e for e in evs if e.get("ev") == "autotune_decision"]
    assert len(decs) == 1
    assert decs[0]["mode"] == "off" and decs[0]["source"] == "off"
    assert not [e for e in evs if e.get("ev") == "autotune_probe"]
