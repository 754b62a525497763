"""Measured on-device kernel autotuner (ROADMAP item 3).

Every hot-path kernel decision used to be hand-coded lore from chip
sessions, scattered through ``ops/learner.py`` as inline conditionals:
the 18-30 MB hist-block pathology band, the ``pallas_ct`` promotion
bound (``ncols * bin_pad <= 2560``), the W-ladder cap at 32, and a deck
of pre-registered but never-applied promotion rules (BENCH_NOTES.md
"Armed decks").  This module inverts that architecture: selection is a
single decision function (`decide`) that treats the old heuristics as
the *prior*, enumerates the 3-6 viable (hist_kernel, wave_width,
precision, fused-iteration) cells for the actual shape, microbenches each
cell for a few waves on the real device with real-shaped data, picks
the winner, and persists it in an on-disk cache keyed by
(shape-bucket, device-kind, schema rev) next to the XLA compile cache
— so subsequent runs on the same shape pay zero tuning cost.

Hard gates are CORRECTNESS constraints and are never tuning candidates:

- the 64 MB VMEM budget (`WAVE_VMEM_GATE`) — cells whose accumulator
  block would not compile are not enumerated;
- the W=1 order-sensitivity quality pin (`resolve_wave_width`) — a
  speed measurement must not undo a quality decision, so a pinned
  width (explicit user width, or the DART/GOSS/lambdarank batched-order
  pin) excludes width from the tuned dimensions entirely (`Pins`).

Modes (``tpu_autotune``):

- ``off``     — prior only; no cache read, no probes (the CPU-CI
                default: selected cells are bit-identical to the
                legacy heuristics, tests/test_autotune.py).
- ``prior``   — use a cached winner when one exists, else the prior;
                never probe.
- ``measure`` — cache hit, else probe the candidate cells and persist
                the winner.
- ``force``   — always re-probe and overwrite the cache entry.

Observability: one ``autotune_decision`` event per learner
construction (whatever the mode — `obs explain` shows *why* a kernel
was chosen, including "heuristic prior, tuning off"), plus one
``autotune_probe`` event per measured cell with its s/wave (schema v8,
obs/events.py).  The learner queues these until its observer is
attached (gbdt.py wires the observer after construction).

Testing: `install_probe_hooks` injects a fake timer and/or a synthetic
bench function (the same injectable-clock pattern as ``SloEngine`` in
obs/serve.py) so winner selection, cache round-trips and invalidation
are deterministic off-TPU — that is also how the CI smoke step runs
measure mode on the CPU backend (tools/autotune_smoke.py).
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax

from ..utils.config import Config
from ..utils.log import Log
from .wave import WAVE_ONLY_MODES, hist_block_bytes

# the VMEM budget the Pallas wave kernels compile under, shared with the
# auto hist-mode gate (64 MB of the kernels' 100 MB compiler limit so
# input tiles and temporaries fit too).  HARD gate: candidate cells
# beyond it are not enumerated, they would not compile.
WAVE_VMEM_GATE = 64 << 20

# The 18-30 MB mid-size accumulator-block pathology band and its
# `band_adjusted_width` escape prior were DELETED in v11: the root cause
# was the wave kernels' row-tile planner sizing input tiles against a
# fixed 16 MB budget that ignored the VMEM-resident accumulator block,
# so exactly the mid-band blocks oversubscribed VMEM under Mosaic
# double-buffering and spilled.  The planner now subtracts the resident
# block from the tile budget (ops/pallas_wave.py::_tile_plan; regression
# probe `tile_plan_vmem_report`), so in-band cells are ordinary measured
# candidates — see docs/FusedIteration.md for the post-mortem.

# the measured pallas_ct promotion bound (ncols * bin_pad) — a PRIOR
# heuristic, not a hard gate: in measure mode ct cells beyond it are
# legitimate candidates (the round-5 "ct-bound widening" armed deck
# becomes a tested cell instead of a dead comment)
CT_PROMOTION_BOUND = 2560

# bump when the meaning of a cached cell changes (new tuned dimension,
# changed probe workload, kernel semantics change): old entries carry
# the old rev in their key and simply stop matching, and `load_cache`
# drops whole files written at another rev so stale entries can never
# be re-merged into a new-rev file by `store_cache`.
# rev 2: cells gained the `fused` dimension (ops/fused_iter.py) and the
# wave kernels' tile plan changed (accumulator-aware budget) — rev-1
# timings measured the old plan and do not transfer.
CACHE_SCHEMA_REV = 2

# enumeration cap — a probe costs a compile + a few waves, and past ~6
# cells the marginal candidate is a long shot (the prior and its
# single-step neighbours cover the measured surprises).  Raised 5 -> 6
# at rev 2 so the fused-iteration flip fits alongside the original four
# neighbour arms.
MAX_CELLS = 6

_CACHE_FILENAME = "autotune_cache.json"


def _order_sensitive(config: Config) -> bool:
    """Configs whose quality measurably depends on the leaf-wise split
    ORDER (PARITY_TRAINING.md: lambdarank NDCG; DART/GOSS/InfiniteBoost
    compound the approximation through tree re-weighting / sampling)."""
    return (str(config.objective) in ("lambdarank", "rank")
            or str(config.boosting_type) in ("dart", "goss", "infinite",
                                             "infiniteboost"))


def resolve_wave_order(config: Config) -> str:
    """tpu_wave_order: auto -> 'exact' where order matters (those configs
    then keep wave-width speed WITH the reference's split sequence),
    'batched' otherwise (proven quality parity at full speed)."""
    v = str(config.tpu_wave_order).strip().lower()
    if v not in ("auto", "batched", "exact"):
        Log.fatal("Unknown tpu_wave_order %s (expected auto/batched/"
                  "exact)", v)
    if v != "auto":
        return v
    return "exact" if _order_sensitive(config) else "batched"


def resolve_wave_width(config: Config, num_leaves: int,
                       wave_order: str = "batched") -> int:
    """tpu_wave_width=-1 -> auto: scale the wave to the frontier size,
    gated on QUALITY, not only speed.

    Speed (v5e, 1M x 28, BENCH_NOTES.md): W=16 is fastest at 63 leaves,
    W=32 at 255 — bigger waves amortize the per-sweep pass over more
    splits, but at small trees they just pad the frontier.

    Quality (PARITY_TRAINING.md): BATCHED frontiers approximate the
    leaf-wise split ORDER; at W=8 the measured deltas vs the reference
    are within ~1e-3 for plain-GBDT binary/multiclass metrics but
    -6.4e-3 NDCG@10 on lambdarank (ranking gains are order-sensitive)
    and +0.9e-2..+3e-2 logloss under DART/GOSS/InfiniteBoost (their
    tree re-weighting / gradient sampling compounds the order
    approximation).  Those configs auto-resolve to tpu_wave_order=exact
    (which reproduces the leaf-wise sequence bit-for-bit at any W,
    tests/test_wave_exact_order.py) and KEEP the width ladder; under an
    explicit tpu_wave_order=batched they fall back to W=1.  Explicit
    user widths always pass through.
    """
    w = int(config.tpu_wave_width)
    if w > 0:
        return w
    if w != -1:
        Log.fatal("tpu_wave_width must be positive or -1 (auto), got %d", w)
    if _order_sensitive(config) and wave_order != "exact":
        # batched waves approximate the split order — these configs pay
        # W=1 unless the exact-order schedule carries them
        return 1
    if num_leaves <= 31:
        return 8
    if num_leaves <= 127:
        return 16
    return 32


def prior_hist_mode(config: Config, ncols: int, bin_pad: int,
                    num_leaves: int, psum_axis: Optional[str],
                    on_tpu: Optional[bool] = None) -> str:
    """The legacy ``tpu_histogram_mode=auto`` heuristic — now the
    autotuner's cache-miss PRIOR and the fallback when tuning is
    disabled or off-TPU.

    Measured on v5e (1M x 28, varying inputs to defeat dispatch dedup):
    onehot 7.2ms/25.6ms at B=63/255 vs scatter 226ms at either — XLA's
    fused one-hot reduce is at the VPU roofline, scatter-add
    serializes.  On CPU the opposite holds.

    On-chip A/B at the 255-leaf recipe (tools/AB_RESULTS.md, 1M x 28):
    the transposed Pallas wave kernel (one-hot generated in VMEM,
    MXU-native dot) beats the XLA one-hot engine 6.60 vs 5.56 it/s —
    and the gap widens with N as the materialized one-hot's HBM floor
    grows.  auto therefore picks it whenever the wave engine will
    actually run it: TPU, f32 accumulation (the kernels are
    single-dtype), the dense store, a learner whose engine is the wave
    schedule (serial/data; voting+feature run the exact engine), and a
    shape whose VMEM-resident histogram block leaves headroom inside
    the kernels' 100 MB compiler budget — the gate uses 64 MB so input
    tiles/temporaries fit too (the A/B covered 28 cols x 63 bins; a
    Bosch-wide 968 x 256-pad block would NOT compile — those shapes
    keep the HBM-streaming onehot engine).

    v5 fused kernel promotion (round-4 on-chip A/Bs): at the narrow-F
    recipe pallas_ct beats pallas_t at BOTH measured shapes — 1.30 vs
    1.16 it/s at the 10.5M x 28 flagship (tools/BENCH_SUITE.md
    higgs_ct) and 11.66 vs 10.92 at 1M x 28 (tools/AB_RESULTS.md) — by
    fusing the partition sweep into the histogram kernel (ONE Xt read
    per wave).  Wide-F shapes keep pallas_t until ct has on-chip
    datapoints there; in measure mode the autotuner now probes exactly
    that arm instead of leaving it queued.  Both ct measurements are
    single-chip serial arms, so the promotion is scoped to serial
    EXECUTION — psum_axis is None, which includes data configs falling
    back to the serial engine on one device (ADVICE r4); the true DP
    learner keeps pallas_t until a DP A/B lands.  Round-5 widening
    (tools/BENCH_SUITE.md 15:50 block): ct won 15% at expo_cat (40 x
    64-pad = 2560, 4.07 vs 3.53 it/s) so the bound moves to that
    measured shape.  It is NOT widened further by hand: msltr's
    0.68-vs-0.66 is within noise, and epsilon (2000 x 64 = 128000)
    LOSES 5.6x (0.40 vs 2.23) — wide-F keeps pallas_t.
    """
    if on_tpu is None:
        on_tpu = jax.default_backend() == "tpu"
    wave_capable = (
        str(config.tpu_growth) in ("auto", "wave")
        and not config.tpu_use_dp
        and not config.tpu_sparse
        and str(config.tree_learner) in ("serial", "data",
                                         "data_parallel"))
    # width only resolved (and validated) when the wave engine will
    # actually run — off-TPU growth resolves to exact here and a
    # garbage tpu_wave_width must keep training (ADVICE r2)
    vmem_hist_bytes = (hist_block_bytes(
        ncols, bin_pad,
        resolve_wave_width(config, num_leaves, resolve_wave_order(config)))
        if on_tpu and wave_capable else 0)
    if on_tpu and wave_capable and vmem_hist_bytes <= WAVE_VMEM_GATE:
        return ("pallas_ct"
                if ncols * bin_pad <= CT_PROMOTION_BOUND
                and psum_axis is None
                else "pallas_t")
    return "onehot" if on_tpu else "scatter"


def prior_hist_hilo(growth: str, psum_axis: Optional[str],
                    hist_mode: str, hist_dtype) -> bool:
    """The legacy ``tpu_hist_precision=auto`` resolution — the
    autotuner's precision PRIOR.

    Applies only where the Pallas wave kernels run.  Round-5 promotion
    (pre-registered rule, BENCH_NOTES.md "Armed decks"; measured
    tools/BENCH_SUITE.md 15:50 + tools/AB_RESULTS.md 16:41 blocks):
    auto -> single-bf16-product for WAVE growth — 2.12 vs 1.30 it/s at
    the 10.5M flagship (1.63x, gate 1.4x) with 13-iter AUC 0.89305 vs
    hi/lo 0.89295 (1.0e-4, gate 1e-3) and 1M AUC 0.9362 vs 0.9357
    (5e-4, gate 1e-3).  The reference ships the same trade as ITS
    default (gpu_use_dp=false, docs/GPU-Performance.md).  Exact growth
    keeps hi/lo — it is the parity anchor (+7.7e-6 at 10.5M) and its
    engines never ran the bf16 kernels.  Scoped to serial EXECUTION
    (psum_axis is None) like the pallas_ct promotion: every bf16 gate
    was measured on single-chip serial arms, so the true DP learner
    keeps hi/lo until a DP A/B lands.
    """
    from .wave import pallas_wave_active
    return not (growth == "wave" and psum_axis is None
                and pallas_wave_active(hist_mode, hist_dtype))


def row_bucket(num_data: int) -> int:
    """Shape-bucket N: the next power of two ≥ num_data.  Nearby dataset
    sizes share a tuned cell (wave cost scales ~linearly in N, so the
    winner is stable inside a 2x band), while the flagship and a unit
    test do not."""
    n, b = max(int(num_data), 1), 1
    while b < n:
        b <<= 1
    return b


class Cell(NamedTuple):
    """One point of the kernel design space: everything the probe
    harness needs to instantiate a wave core standalone beyond the
    learner's fixed statics."""
    hist_mode: str      # pallas_t / pallas_ct
    wave_width: int     # W
    hist_hilo: bool     # True = hi/lo f32 pair, False = single-bf16
    # rev 2: run the whole iteration as one fused device program
    # (ops/fused_iter.py) instead of the staged gradient/grow/score
    # entry chain — a measured dimension because fusion trades XLA
    # scheduling freedom for zero host orchestration
    fused: bool = False

    def as_dict(self) -> dict:
        return {"hist_mode": self.hist_mode,
                "wave_width": int(self.wave_width),
                "hist_hilo": bool(self.hist_hilo),
                "fused": bool(self.fused)}

    @classmethod
    def from_dict(cls, d: dict) -> "Cell":
        # an older cache's "compact" key is ignored: the row slab is not
        # a dimension (ops/wave.py decides it a wave from a row count)
        return cls(str(d["hist_mode"]), int(d["wave_width"]),
                   bool(d["hist_hilo"]), bool(d.get("fused", False)))


class ShapeBucket(NamedTuple):
    """The cache/decision key: what the measured surface actually
    varies over.  ncols/bin_pad set the accumulator block, num_leaves
    sets the frontier, n_bucket (power-of-two row count) the sweep
    length."""
    ncols: int
    bin_pad: int
    num_leaves: int
    n_bucket: int

    def key(self) -> str:
        return "c%d_b%d_l%d_n%d" % self


class Pins(NamedTuple):
    """Dimensions excluded from tuning (True = pinned).  Pins encode
    explicit user choices and quality gates — correctness constraints,
    not candidates — and are re-applied to cached winners so a cache
    entry tuned under different pins cannot override them."""
    kernel: bool = False
    width: bool = False
    precision: bool = False
    fused: bool = False


class Decision(NamedTuple):
    """What `decide` resolved, plus the observability trail: ``events``
    is a list of (ev, fields) the caller queues on its observer."""
    cell: Cell
    mode: str            # off / prior / measure / force
    source: str          # off / ineligible / prior / cache / measured
    bucket: str
    probes: Tuple        # ((Cell, s_per_wave), ...) measured this call
    margin: float        # runner-up s/wave over winner's, minus 1
    overhead_s: float    # probe seconds paid in this construction
    cache_hit: bool
    events: List


def resolve_mode(config: Config) -> str:
    v = str(config.tpu_autotune).strip().lower()
    if v not in ("off", "prior", "measure", "force"):
        Log.fatal("Unknown tpu_autotune %s (expected off/prior/measure/"
                  "force)", config.tpu_autotune)
    return v


def resolve_cache_path(config: Config) -> str:
    """``tpu_autotune_cache`` when set, else ``autotune_cache.json`` in
    the compile-cache directory (utils/common.py compilation_cache_dir)."""
    p = str(config.tpu_autotune_cache).strip()
    if p:
        return p
    from ..utils.common import compilation_cache_dir
    return os.path.join(compilation_cache_dir(), _CACHE_FILENAME)


def cache_key(device_kind: str, bucket: ShapeBucket) -> str:
    return "%s|v%d|%s" % (device_kind, CACHE_SCHEMA_REV, bucket.key())


def _device_kind() -> str:
    try:
        return str(jax.devices()[0].device_kind).strip().replace(" ", "_")
    except Exception:
        return jax.default_backend()


def load_cache(path: str) -> dict:
    """Read the cache file; a missing or corrupt file is an empty cache
    (the tuner must never take training down).

    A file written at another ``CACHE_SCHEMA_REV`` is ALSO an empty
    cache: its entries were measured against different cell semantics
    (and carry old-rev keys), and returning them here would let
    ``store_cache`` re-merge them — verbatim, pins and all — into a
    file it then stamps with the new rev, resurrecting stale winners
    forever.  Dropping the whole file invalidates cleanly; the next
    measure-mode run re-probes (tests/test_autotune.py)."""
    try:
        with open(path) as f:
            data = json.load(f)
        if data.get("version") != CACHE_SCHEMA_REV:
            return {}
        entries = data.get("entries", {})
        return entries if isinstance(entries, dict) else {}
    except (OSError, ValueError, AttributeError):
        return {}


def store_cache(path: str, key: str, entry: dict) -> bool:
    """Merge ``key: entry`` into the cache file atomically (tmp +
    os.replace, same crash-safety idiom as the event writer's barriers).
    Returns False — without raising — when the cache dir is unwritable."""
    try:
        entries = load_cache(path)
        entries[key] = entry
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp.%d" % os.getpid()
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_SCHEMA_REV, "entries": entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return True
    except OSError as e:
        Log.warning("autotune cache not persisted to %s (%s); this run "
                    "keeps its measured cell, the next run re-probes",
                    path, e)
        return False


def apply_pins(cell: Cell, prior: Cell, pins: Pins) -> Cell:
    """Pinned dimensions always take the prior's (validated) value —
    a cached winner tuned under different pins must not override an
    explicit user choice or a quality gate."""
    return Cell(
        hist_mode=prior.hist_mode if pins.kernel else cell.hist_mode,
        wave_width=prior.wave_width if pins.width else cell.wave_width,
        hist_hilo=prior.hist_hilo if pins.precision else cell.hist_hilo,
        fused=prior.fused if pins.fused else cell.fused)


def enumerate_cells(prior: Cell, bucket: ShapeBucket, pins: Pins,
                    ct_allowed: bool = True) -> List[Cell]:
    """The 3-6 candidate cells: the prior plus its single-step
    neighbours along each unpinned dimension, hard-gated on VMEM.

    Neighbour choices mirror the measured surprises: width one tier up
    or down (the band pathology and the W-ladder cap were both
    width-tier effects), the alternate transposed kernel (the round-5
    "ct-bound widening" arm — ct beyond 2560 is a candidate here, not
    a dead comment) and the flipped precision (the bf16 armed deck).
    The prior is always candidate 0 so a tie keeps the
    measured-by-default choice.
    """
    if prior.hist_mode not in WAVE_ONLY_MODES:
        # width and precision are wave-kernel dimensions; other
        # engines have no neighbours to probe
        return [prior]
    cands: List[Cell] = [prior]
    if not pins.fused:
        # the staged/fused flip (rev 2): same kernels, different entry
        # granularity — measured because fusing removes host gaps but
        # also removes XLA's freedom to overlap the stages.  Enumerated
        # FIRST among the neighbours: it is the rev-2 headline dimension
        # and must not fall off the MAX_CELLS tail when every other
        # dimension is unpinned too.
        cands.append(prior._replace(fused=not prior.fused))
    if not pins.width:
        for w in (prior.wave_width * 2, prior.wave_width // 2):
            if 1 <= w <= 64:
                cands.append(prior._replace(wave_width=w))
    if not pins.kernel:
        alt = {"pallas_t": "pallas_ct",
               "pallas_ct": "pallas_t"}.get(prior.hist_mode)
        if alt and (alt != "pallas_ct" or ct_allowed):
            cands.append(prior._replace(hist_mode=alt))
    if not pins.precision:
        cands.append(prior._replace(hist_hilo=not prior.hist_hilo))
    out: List[Cell] = []
    for c in cands:
        if c in out:
            continue
        # HARD gate: the accumulator block must leave VMEM headroom —
        # same budget as the prior's auto promotion.  The prior itself
        # is exempt: it is the already-validated fallback.
        if c is not prior and hist_block_bytes(
                bucket.ncols, bucket.bin_pad,
                c.wave_width) > WAVE_VMEM_GATE:
            continue
        out.append(c)
    return out[:MAX_CELLS]


# ---------------------------------------------------------------- probing
# injectable measurement hooks (the SloEngine injectable-clock pattern,
# obs/serve.py): "timer" replaces time.perf_counter, "bench" replaces
# the whole build+run probe with a synthetic (cell, bucket) -> s/wave,
# "force" lets measure mode probe off-TPU — tests and the CI CPU smoke
# install these; production never touches them
_HOOKS = {"timer": None, "bench": None, "force": False}


def install_probe_hooks(timer: Optional[Callable[[], float]] = None,
                        bench: Optional[Callable] = None,
                        force: bool = True) -> None:
    _HOOKS["timer"] = timer
    _HOOKS["bench"] = bench
    _HOOKS["force"] = bool(force)


def clear_probe_hooks() -> None:
    _HOOKS["timer"] = None
    _HOOKS["bench"] = None
    _HOOKS["force"] = False


def probe_available(probe) -> bool:
    """Probing needs a real device (or an injected bench/force hook):
    measure mode off-TPU is a documented no-op falling back to the
    prior — CPU CI must not pay wave compiles per shape."""
    if _HOOKS["bench"] is not None:
        return True
    if probe is None:
        return False
    return jax.default_backend() == "tpu" or _HOOKS["force"]


def measure_cells(cells: List[Cell], bucket: ShapeBucket, probe,
                  waves: int, events: List) -> List[Tuple[Cell, float]]:
    """Microbench each candidate: build the cell's core via ``probe``
    (compile + one warmup wave outside the timed window), then time
    ``waves`` waves and record s/wave.  A cell whose build or run
    raises (e.g. a Mosaic compile failure on an untested shape) is
    skipped with a warning — a failed candidate must never take
    training down, the prior still works."""
    timer = _HOOKS["timer"] or time.perf_counter
    bench = _HOOKS["bench"]
    waves = max(1, int(waves))
    out: List[Tuple[Cell, float]] = []
    for cell in cells:
        try:
            if bench is not None:
                s_per_wave = float(bench(cell, bucket))
            else:
                run = probe(cell)
                run()                      # compile + warmup, untimed
                t0 = timer()
                for _ in range(waves):
                    run()
                s_per_wave = (timer() - t0) / waves
        except Exception as e:  # noqa: BLE001 — any candidate may fail
            Log.warning("autotune probe failed for cell %s on %s (%s); "
                        "candidate dropped", cell, bucket.key(), e)
            continue
        events.append(("autotune_probe", {
            "bucket": bucket.key(), "cell": cell.as_dict(),
            "waves": waves, "s_per_wave": s_per_wave,
            "roofline": _cell_roofline(bucket, cell, s_per_wave)}))
        out.append((cell, s_per_wave))
    return out


def _cell_roofline(bucket: ShapeBucket, cell: Cell, s_per_wave: float):
    """Schema-13 roofline stamp for one probed cell (obs/roofline.py):
    where its measured s/wave sits against this chip's compute and
    memory roofs, so `obs explain` can say why the winner won.
    Best-effort — attribution must never fail a probe."""
    try:
        from ..obs.roofline import cell_roofline
        return cell_roofline(bucket, cell, s_per_wave,
                             kind=_device_kind())
    except Exception:  # noqa: BLE001 — stamp is telemetry, not control
        return None


def decide(config: Config, bucket: ShapeBucket, prior: Cell, pins: Pins,
           eligible: bool, probe=None,
           ct_allowed: bool = True) -> Decision:
    """The single kernel-selection decision for one learner
    construction.  Always returns a Decision carrying exactly one
    ``autotune_decision`` event (plus any probe events) so the timeline
    records why the kernel was chosen even when tuning is off."""
    mode = resolve_mode(config)
    waves = int(config.tpu_autotune_waves)
    if waves <= 0:
        Log.fatal("tpu_autotune_waves must be positive, got %d", waves)
    events: List = []

    def _finish(cell, source, probes=(), margin=0.0, overhead=0.0,
                cache_hit=False, cache_path=""):
        events.append(("autotune_decision", {
            "mode": mode, "source": source, "bucket": bucket.key(),
            "device_kind": _device_kind(), "cell": cell.as_dict(),
            "prior": prior.as_dict(),
            "cells": [{"cell": c.as_dict(), "s_per_wave": s,
                       "roofline": _cell_roofline(bucket, c, s)}
                      for c, s in probes],
            "margin": float(margin), "overhead_s": float(overhead),
            "cache_hit": bool(cache_hit), "cache_path": cache_path}))
        return Decision(cell=cell, mode=mode, source=source,
                        bucket=bucket.key(), probes=tuple(probes),
                        margin=float(margin), overhead_s=float(overhead),
                        cache_hit=bool(cache_hit), events=events)

    if mode == "off":
        return _finish(prior, "off")
    if not eligible:
        return _finish(prior, "ineligible")
    path = resolve_cache_path(config)
    key = cache_key(_device_kind(), bucket)
    if mode != "force":
        entry = load_cache(path).get(key)
        if entry is not None:
            try:
                cell = apply_pins(Cell.from_dict(entry["cell"]), prior,
                                  pins)
            except (KeyError, TypeError, ValueError):
                cell = None
            if cell is not None:
                return _finish(cell, "cache", cache_hit=True,
                               cache_path=path)
    if mode == "prior" or not probe_available(probe):
        # prior mode never probes; measure/force off-device (no TPU, no
        # injected bench) fall back to the prior — documented no-op
        return _finish(prior, "prior", cache_path=path)
    cells = enumerate_cells(prior, bucket, pins, ct_allowed=ct_allowed)
    probes = measure_cells(cells, bucket, probe, waves, events)
    if not probes:
        return _finish(prior, "prior", cache_path=path)
    best = min(probes, key=lambda p: p[1])
    others = sorted(s for c, s in probes if c is not best[0])
    margin = (others[0] / best[1] - 1.0) if others and best[1] > 0 else 0.0
    overhead = sum(s * waves for _, s in probes)
    store_cache(path, key, {
        "cell": best[0].as_dict(), "s_per_wave": best[1],
        "waves": waves,
        "cells": [{"cell": c.as_dict(), "s_per_wave": s}
                  for c, s in probes]})
    return _finish(best[0], "measured", probes=probes, margin=margin,
                   overhead=overhead, cache_path=path)
