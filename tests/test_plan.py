"""The execution plan (ops/plan.py): what ``auto`` and the ``tpu_*`` keys
resolve to, pinned whole.

``resolve_plan`` takes the backend as a value, so the rows the chip runs
(the benchmark's own three configurations on a TPU, one chip and under
the data mesh) are pinned here on a CPU.  `PLANS` was written by running
the PARENT's constructors (commit 874dc88, `jax.default_backend` patched
where a row says ``tpu``) on a stub dataset of the row's shape, not by
reading the function under test; a sweep of 1,500 configurations through
both trees' constructors (plans, warnings and fatals compared; kept out of
the tree) agreed row for row.

The tile-plan tests pin the planner (ops/pallas_wave.py _tile_plan) on
the cells the deleted 18-30 MB band rule used to bend: its root cause
(the row-tile planner ignoring the VMEM-resident accumulator block) is
fixed there.
"""
import io
import json
import os

import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import plan
from lightgbm_tpu.ops.plan import (Plan, prior_hist_mode, resolve_plan,
                                   resolve_wave_order, resolve_wave_width,
                                   store_bin_width, store_col_pads)
from lightgbm_tpu.utils.config import Config
from lightgbm_tpu.utils.log import LightGBMError, Log

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, F = True, False


with open(os.path.join(REPO, "benchmark", "configs", "expo_700.json")) as _f:
    EXPO = json.load(_f)["params"]     # the cell's recipe, as it is filed


def _cfg(num_leaves, **kw):
    kw.setdefault("verbose", -1)
    kw["num_leaves"] = num_leaves
    return Config(kw)


# ------------------------------------------------------------ the whole plan

# (id, params or the name of a benchmark configuration whose `params` are
#  read as they stand, ncols, nbins, backend, psum_axis, dense_device_data,
#  (hist_mode, growth, wave_order, wave_width, hist_hilo, wave_lookup,
#   packed_cols, sparse,
#   cache_hists, wave_chunk, pallas_interpret, kernel_runs, needs_xt, slab))
# The mesh learners bring a dense device matrix of their own; data and
# voting pass psum_axis="data", the feature learner passes none.
PLANS = [
    ("epsilon_2000-tpu", "epsilon_2000",
     None, None, "tpu", None, F,
     ("pallas_t", "wave", "batched", 32, F, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("bosch_968-tpu", "bosch_968",
     None, None, "tpu", None, F,
     ("pallas_t", "wave", "batched", 32, F, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("epsilon_2000_dp4-tpu", "epsilon_2000_dp4",
     None, None, "tpu", "data", T,
     ("pallas_t", "wave", "batched", 32, T, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("higgs_28-tpu", "higgs_28",
     None, None, "tpu", None, F,
     ("pallas_ct", "wave", "batched", 32, F, "compact", 0, "",
      T, 16384, F, T, T, F)),
    ("epsilon_2000-cpu", "epsilon_2000",
     None, None, "cpu", None, F,
     ("scatter", "exact", "batched", 1, T, "onehot", 0, "",
      T, 16384, F, F, F, F)),
    ("bosch_968-cpu", "bosch_968",
     None, None, "cpu", None, F,
     ("scatter", "exact", "batched", 1, T, "onehot", 0, "",
      T, 16384, F, F, F, F)),
    ("epsilon_2000_dp4-cpu", "epsilon_2000_dp4",
     None, None, "cpu", "data", T,
     ("scatter", "exact", "batched", 1, T, "onehot", 0, "",
      T, 16384, F, F, F, F)),
    ("lambdarank", {"objective": "lambdarank", "num_leaves": 255},
     136, 255, "tpu", None, F,
     ("pallas_t", "wave", "exact", 32, F, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("dart", {"num_leaves": 255, "boosting": "dart"},
     136, 255, "tpu", None, F,
     ("pallas_t", "wave", "exact", 32, F, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("goss", {"num_leaves": 255, "boosting": "goss"},
     136, 255, "tpu", None, F,
     ("pallas_t", "wave", "exact", 32, F, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("infiniteboost", {"num_leaves": 255, "boosting": "infiniteboost"},
     136, 255, "tpu", None, F,
     ("pallas_t", "wave", "exact", 32, F, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("use_dp", {"num_leaves": 255, "tpu_use_dp": T},
     136, 255, "tpu", None, F,
     ("onehot", "wave", "batched", 32, T, "compact", 0, "",
      T, 16384, F, F, F, F)),
    ("cols28-serial", {"num_leaves": 255},
     28, 63, "tpu", None, F,
     ("pallas_ct", "wave", "batched", 32, F, "compact", 0, "",
      T, 16384, F, T, T, F)),
    ("cols28-mesh", {"num_leaves": 255, "tree_learner": "data"},
     28, 63, "tpu", "data", T,
     ("pallas_t", "wave", "batched", 32, T, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("bosch-256pad-over-vmem-gate", {"num_leaves": 255},
     968, 255, "tpu", None, F,
     ("onehot", "wave", "batched", 32, T, "compact", 0, "",
      T, 16384, F, F, F, F)),
    ("feature-learner", {"num_leaves": 255, "tree_learner": "feature"},
     136, 63, "tpu", None, T,
     ("onehot", "wave", "batched", 32, T, "compact", 0, "",
      T, 16384, F, F, F, F)),
    ("voting-learner", {"num_leaves": 255, "tree_learner": "voting"},
     136, 63, "tpu", "data", T,
     ("onehot", "wave", "batched", 32, T, "compact", 0, "",
      T, 16384, F, F, F, F)),
    ("sparse-coo", {"num_leaves": 255, "tpu_sparse": T},
     136, 63, "tpu", None, F,
     ("sparse", "exact", "batched", 1, T, "onehot", 0, "coo",
      T, 16384, F, F, F, F)),
    ("sparse-mxu", {"num_leaves": 255, "tpu_sparse": T,
      "tpu_sparse_kernel": T},
     136, 63, "tpu", None, F,
     ("sparse_mxu", "wave", "batched", 32, T, "compact", 0, "mxu",
      T, 16384, F, F, F, F)),
    ("bins15-serial", {"num_leaves": 63},
     136, 16, "tpu", None, F,
     ("pallas_t", "wave", "batched", 16, F, "compact", 136, "",
      T, 16384, F, T, T, T)),
    ("bins15-mesh", {"num_leaves": 63, "tree_learner": "data"},
     136, 16, "tpu", "data", T,
     ("pallas_t", "wave", "batched", 16, T, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("interpret-cpu", {"num_leaves": 31, "tpu_histogram_mode": "pallas_t",
      "tpu_pallas_interpret": T},
     28, 63, "cpu", None, F,
     ("pallas_t", "wave", "batched", 8, T, "onehot", 0, "",
      T, 16384, T, F, F, T)),
    ("interpret-tpu", {"num_leaves": 31, "tpu_pallas_interpret": T},
     28, 63, "tpu", None, F,
     ("pallas_ct", "wave", "batched", 8, F, "compact", 0, "",
      T, 16384, F, T, T, F)),
    ("explicit-hist-mode", {"num_leaves": 255, "tpu_histogram_mode": "onehot",
      },
     136, 63, "tpu", None, F,
     ("onehot", "wave", "batched", 32, T, "compact", 0, "",
      T, 16384, F, F, F, F)),
    ("explicit-wave-width", {"num_leaves": 255, "tpu_wave_width": 8},
     136, 63, "tpu", None, F,
     ("pallas_t", "wave", "batched", 8, F, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("explicit-hist-precision",
     {"num_leaves": 255, "tpu_hist_precision": "hilo"},
     136, 63, "tpu", None, F,
     ("pallas_t", "wave", "batched", 32, T, "compact", 0, "",
      T, 16384, F, T, T, T)),
    ("explicit-wave-lookup", {"num_leaves": 255, "tpu_wave_lookup": "gather"},
     28, 63, "tpu", None, F,
     ("pallas_ct", "wave", "batched", 32, F, "gather", 0, "",
      T, 16384, F, T, T, F)),
    ("explicit-growth", {"num_leaves": 255, "tpu_growth": "exact"},
     136, 63, "tpu", None, F,
     ("onehot", "exact", "batched", 1, T, "onehot", 0, "",
      T, 16384, F, F, F, F)),
    # `expo_700` (PR 35): the store is what EFB makes of the 700 one-hot
    # columns, 10 groups of up to 256 bins, so 10 x a 256-bin pad sits ON
    # `CT_PROMOTION_BOUND`: the side the cell runs on, and its neighbours
    ("expo_700-10-groups-tpu", EXPO, 10, 256, "tpu", None, F,
     ("pallas_ct", "wave", "batched", 32, F, "compact", 0, "",
      T, 16384, F, T, T, F)),
    ("expo_700-9-groups-tpu", EXPO, 9, 256, "tpu", None, F,
     ("pallas_ct", "wave", "batched", 32, F, "compact", 0, "",
      T, 16384, F, T, T, F)),
    ("expo_700-11-groups-tpu", EXPO, 11, 256, "tpu", None, F,
     ("pallas_t", "wave", "batched", 32, F, "compact", 0, "",
      T, 16384, F, T, T, T)),
]


def _case(params, ncols, nbins):
    if isinstance(params, str):
        with open(os.path.join(REPO, "benchmark", "configs",
                               params + ".json")) as f:
            conf = json.load(f)
        return (Config(dict(conf["params"])), conf["columns"],
                int(conf["params"]["max_bin"]))
    return (Config(dict({"objective": "binary", "verbose": -1}, **params)),
            ncols, nbins)


def _resolve(config, ncols, nbins, backend, psum_axis=None, dense=False):
    return resolve_plan(
        config, ncols=ncols, nbins=nbins, num_leaves=config.num_leaves,
        bins_per_col=[nbins] * ncols, backend=backend,
        dtype=jnp.float64 if config.tpu_use_dp else jnp.float32,
        psum_axis=psum_axis, dense_device_data=dense)


@pytest.mark.parametrize(
    "params,ncols,nbins,backend,psum_axis,dense,want",
    [row[1:] for row in PLANS], ids=[row[0] for row in PLANS])
def test_resolve_plan_pins_the_whole_plan(params, ncols, nbins, backend,
                                          psum_axis, dense, want):
    config, ncols, nbins = _case(params, ncols, nbins)
    got = _resolve(config, ncols, nbins, backend, psum_axis, dense)
    assert got == Plan(*want)
    # tpu_fused_iter=auto wishes the fused step where a compiled kernel
    # runs: both one-chip cells (the mesh's wish is refused later, by
    # ops/fused_iter.py fused_supported)
    assert got.fused_wanted == got.kernel_runs


# the cell's ten EFB groups (benchmark/configs/expo_700.json, `assumed`)
EXPO_BINS = (13, 32, 8, 23, 256, 59, 256, 59, 63, 63)


@pytest.mark.parametrize("params,backend,nbins,width,padded", [
    # pallas_ct: each group its own bins by granules of 32
    (EXPO, "tpu", 256, 256, 4 * 32 + 256 + 64 + 256 + 3 * 64),
    (dict(EXPO, max_bin=63), "cpu", 200, 200, 10 * 200),  # the XLA engines
    (dict(EXPO, tpu_growth="wave", tpu_histogram_mode="pallas_t",
          tpu_pallas_interpret=True), "cpu", 200, 256, 10 * 256),
    (dict(EXPO, tpu_growth="wave", tpu_histogram_mode="pallas_ct",
          tpu_pallas_interpret=True), "cpu", 256, 256, 896),
], ids=["tpu-kernel", "cpu-xla", "cpu-interpreter", "cpu-interpreter-ct"])
def test_store_bin_width_is_padded_where_a_wave_kernel_runs(
        params, backend, nbins, width, padded):
    """The `bundle` counter's `group_bins_padded` (ops/learner.py) is the
    one-hot rows the plan's engine multiplies a table row against: under
    the fused kernel the sum of the groups' own widths
    (`store_col_pads`), else the groups times `store_bin_width`:
    `_bin_pad` where the plan takes another Pallas wave kernel, the bins
    as they are under every other engine."""
    config, ncols, nbins = _case(params, 10, nbins)
    plan = _resolve(config, ncols, nbins, backend)
    assert store_bin_width(plan, nbins) == width
    pads = store_col_pads(plan, [min(b, nbins) for b in EXPO_BINS], nbins)
    assert (sum(pads) or ncols * store_bin_width(plan, nbins)) == padded
    assert bool(pads) == (plan.hist_mode == "pallas_ct")
    # a rare-level pair of 62 / 56 bins is the same layout: one program
    assert store_col_pads(
        plan, (13, 32, 8, 23, nbins, 62, nbins, 56, 63, 63), nbins) == pads
    # and a store whose columns all fill the uniform pad has none
    assert store_col_pads(plan, [nbins] * 10, nbins) == ()


@pytest.mark.parametrize("groups", [9, 10, 11])
def test_the_plan_is_judged_on_the_uniform_block(groups):
    """`auto` reads `ncols * _bin_pad(nbins)` (`CT_PROMOTION_BOUND`,
    `WAVE_VMEM_GATE`), not the ragged sum: a store of 9, 10 or 11 groups
    with Expo's own bin counts resolves as the pinned rows above say, the
    eleventh group to pallas_t and the slab although its ragged sum
    (896 + 64) is far under the bound (ROADMAP.md A7d)."""
    config = Config(dict({"objective": "binary", "verbose": -1}, **EXPO))
    bins = (EXPO_BINS + (63,))[:groups]
    got = resolve_plan(
        config, ncols=groups, nbins=256, num_leaves=config.num_leaves,
        bins_per_col=bins, backend="tpu", dtype=jnp.float32,
        psum_axis=None, dense_device_data=False)
    (want,) = [row[-1] for row in PLANS
               if row[0] == "expo_700-%d-groups-tpu" % groups]
    assert got == Plan(*want)
    assert bool(store_col_pads(got, bins, 256)) == (groups <= 10)


# every check of a key moved with its rule, word for word: the parent's
# messages (from the same sweep), a few of each kind
MESSAGES = [
    ("fatal", {"tpu_histogram_mode": "bogus"}, "tpu",
     "Unknown tpu_histogram_mode bogus (expected auto/onehot/scatter/"
     "pallas/pallas_t/pallas_ct)"),
    ("fatal", {"tpu_growth": "exact", "tpu_histogram_mode": "pallas_t"},
     "tpu", "tpu_histogram_mode=pallas_t requires tpu_growth=wave (this "
     "kernel is wave-only)"),
    ("fatal", {"tpu_sparse": True, "tpu_histogram_mode": "pallas_t"},
     "cpu", "tpu_sparse=true is incompatible with tpu_histogram_mode="
     "pallas_t (the pallas kernels are dense-only)"),
    ("fatal", {"tpu_wave_chunk": 0}, "cpu",
     "tpu_wave_chunk must be positive, got 0"),
    ("fatal", {"tpu_wave_width": 0}, "tpu",
     "tpu_wave_width must be positive or -1 (auto), got 0"),
    ("warning", {"tpu_sparse_kernel": True}, "cpu",
     "tpu_sparse_kernel=true has no effect without tpu_sparse=true"),
    ("warning", {"tpu_wave_lookup": "gather"}, "tpu",
     "tpu_wave_lookup=gather has no effect under tpu_histogram_mode="
     "pallas_ct (the fused kernels / sparse pass own their own lookup)"),
    ("warning", {"tpu_pallas_interpret": True}, "tpu",
     "tpu_pallas_interpret=true ignored on TPU (the compiled Pallas "
     "kernels run)"),
    ("warning", {"tpu_wave_chunk": 128, "tpu_growth": "wave"}, "cpu",
     "tpu_wave_chunk=128 is below the engine minimum; the wave sweep "
     "uses 256-row chunks instead"),
]


@pytest.mark.parametrize("kind,params,backend,text", MESSAGES,
                         ids=["%s-%s" % (m[0], "-".join(m[1]))
                              for m in MESSAGES])
def test_resolve_plan_checks_the_keys_it_reads(kind, params, backend, text):
    config = Config(dict({"objective": "binary", "num_leaves": 31,
                          "verbose": 0}, **params))
    buf = io.StringIO()
    prev, level = Log.set_stream(buf), Log._level
    try:
        Log.reset_level(0)
        if kind == "fatal":
            with pytest.raises(LightGBMError) as err:
                _resolve(config, 28, 63, backend)
            assert str(err.value) == text
        else:
            _resolve(config, 28, 63, backend)
            assert "[Warning] " + text in buf.getvalue()
    finally:
        Log.set_stream(prev)
        Log.reset_level(level)


def test_learner_attributes_are_reads_of_the_plan():
    """The names the mesh learners, ops/fused_iter.py and obs_info know
    (`hist_mode`, `wave_width`, `wave_compact`, ...) read the one plan."""
    import numpy as np
    from lightgbm_tpu.io.dataset import TrainingData
    from lightgbm_tpu.ops.learner import SerialTreeLearner

    rng = np.random.default_rng(5)
    X = rng.normal(size=(300, 4))
    cfg = Config({"num_leaves": 7, "verbose": -1, "tpu_growth": "wave"})
    td = TrainingData.from_matrix(X, label=(X[:, 0] > 0).astype(float),
                                  config=cfg)
    lrn = SerialTreeLearner(cfg, td)
    p = lrn.plan
    assert (lrn.hist_mode, lrn.growth, lrn.wave_width, lrn.hist_hilo,
            lrn.wave_compact, lrn.sparse_on) == (
        p.hist_mode, "wave", 8, p.hist_hilo, p.slab, False)
    assert lrn.obs_info() == {
        "learner": "SerialTreeLearner", "growth": "wave",
        "hist_mode": p.hist_mode, "wave_width": 8,
        "wave_order": "batched", "wave_lookup": p.wave_lookup,
        "hist_hilo": p.hist_hilo, "wave_compact": False,
        "pallas_interpret": False, "packed_cols": 0, "num_leaves": 7,
        "num_bins": lrn.num_bins, "dtype": "float32", "cache_hists": True}
    with pytest.raises(AttributeError):
        lrn.hist_mode = "onehot"


# ------------------------------------------------- the rules, shape by shape

# the shapes of the reference's published table and what the hand rules
# pick for them on a TPU (ncols, bin_pad, num_leaves, mode, width)
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
    got_mode = prior_hist_mode(cfg, ncols, bin_pad, leaves, None, "tpu")
    assert got_mode == mode, name
    if width is not None:
        w = resolve_wave_width(cfg, leaves, resolve_wave_order(cfg))
        assert w == width, name


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
    assert not hasattr(plan, "HIST_BLOCK_BAND")
    assert not hasattr(plan, "band_adjusted_width")


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
