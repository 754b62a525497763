"""Vectorized best-split search over (feature, bin) histograms — XLA native.

Parity target: src/treelearner/feature_histogram.hpp:78-387.  The reference
scans each feature's histogram sequentially (up to 3 passes to place the
zero/default bin left, right, or in natural position).  Here a leaf's (F, B)
histogram is read once: the two running sums of its three channels over the
bins, without the default bin (`below[t]`: bins <= t, `above[t]`: bins >= t;
on the TPU two contractions with a triangular matrix on the MXU, at
float32 accuracy, off it the cumulative sums), and every pass's sums at
every split point t are those or derived from them and the entry `xd` at
the feature's default bin:

* zero_left  (dir=-1): right[t] = above[t]
* natural    (dir=-1): right[t] = above[t] + (default >= t ? xd : 0), on
  the TPU; off it the cumulative sum over all the bins, as before
* zero_right (dir=+1): left[t]  = below[t]
* categorical one-vs-rest: left[t] = x[t], no sum over the bins

with the other side the leaf's totals less that one, as the reference has
it: what a pass accumulates is a sum of the bins it holds, never a
difference of two larger sums.  No cumulative sum a pass and channel, no
reversed copy, no gather (what is wanted at the arg-max comes out by a
masked reduction over the bins) — no per-feature loop, no host
round-trips.  Tie-breaking reproduces the reference's iteration order:

* dir=-1 passes iterate bins high->low with strict ``>`` updates, so equal
  gains keep the LARGER threshold; dir=+1 keeps the smaller.
* across passes, earlier passes win ties (strict ``>`` replacement,
  feature_histogram.hpp:88-97);
* across features, the smaller feature index wins ties
  (SplitInfo comparison, split_info.hpp:102-107 — argmax picks first max).

Gain / leaf-output formulas with L1/L2 and the kEpsilon seeding match
GetLeafSplitGain / CalculateSplittedLeafOutput (feature_histogram.hpp:230-249)
in the histograms' own dtype: nothing in the search is narrower.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

kEpsilon = 1e-15

# packed SplitInfo layout (one float vector per leaf; device-resident)
GAIN = 0
FEATURE = 1
THRESHOLD = 2
DEFAULT_BIN_FOR_ZERO = 3
LEFT_OUTPUT = 4
RIGHT_OUTPUT = 5
LEFT_SUM_G = 6
LEFT_SUM_H = 7
LEFT_COUNT = 8
RIGHT_SUM_G = 9
RIGHT_SUM_H = 10
RIGHT_COUNT = 11
IS_CAT = 12
# runner-up feature and its gain (split-audit margin: how close the
# second-best feature came); SECOND_FEATURE is -1 and SECOND_GAIN 0 when
# no other feature had a valid split
SECOND_FEATURE = 13
SECOND_GAIN = 14
SPLIT_VEC_SIZE = 15


class FeatureMeta(NamedTuple):
    """Static per-inner-feature arrays living on device."""
    num_bin: jnp.ndarray        # (F,) int32
    default_bin: jnp.ndarray    # (F,) int32
    is_categorical: jnp.ndarray  # (F,) bool


class SplitParams(NamedTuple):
    """Python-scalar hyperparameters (static under jit closure)."""
    lambda_l1: float
    lambda_l2: float
    min_gain_to_split: float
    min_data_in_leaf: float
    min_sum_hessian_in_leaf: float
    use_missing: bool


def _leaf_split_gain(sum_g, sum_h, l1, l2):
    """GetLeafSplitGain (feature_histogram.hpp:230-236)."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return reg * reg / (sum_h + l2)


def _leaf_output(sum_g, sum_h, l1, l2):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:244-249)."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return -jnp.sign(sum_g) * reg / (sum_h + l2)


class _Cand(NamedTuple):
    gain: jnp.ndarray       # (F,) candidate gain, -inf when invalid
    threshold: jnp.ndarray  # (F,) int32
    dbz: jnp.ndarray        # (F,) int32 default_bin_for_zero
    left_g: jnp.ndarray     # (F,)
    left_h: jnp.ndarray     # (F,) includes +kEpsilon seed
    left_c: jnp.ndarray     # (F,)


def _scan_sums(x, default_bin):
    """What the three numerical passes accumulate, for every split point t
    of x (F, B, 3): `below`, the bins <= t, and `above`, the bins >= t,
    both without the feature's default bin (the ascending and the
    descending scan that skip it), and `above_all`, the bins >= t with it
    (the natural scan).

    On the TPU a `jnp.cumsum` lowers to a reduce-window that adds all B
    bins for every element, and a descending one needs two reversed copies
    besides.  There the bins are contracted with the two triangular 0/1
    matrices on the MXU (two matmuls: side by side as one (B, 2B) matrix
    they cost a relayout of the block and a copy a half, 6.3 against 3.0
    ms for 64 leaves x 2,000 features, PR 31), and the natural scan is the
    descending one plus the default bin's entry where the scan has passed
    it.  The matrices are exact in bfloat16, so at HIGHEST precision
    (three bfloat16 terms of x, accumulated in float32) every product is
    exact: an integer count below 2^24 comes out exact to the bit, an
    empty bin leaves its neighbour's sum as it is (equal gains stay
    equal, so the scan order decides them), and the result does not
    depend on how many leaves are searched at once.  Off the TPU the three
    cumulative sums stay as they were: XLA:CPU's dot adds in an order that
    follows the batch, and exact-order waves must give the trees of W=1
    at any W."""
    bins = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
    zero = jnp.zeros((), x.dtype)
    skip = (bins == default_bin)[..., None]
    x_skip = jnp.where(skip, zero, x)

    def mxu(x, x_skip):
        upto = (bins.T <= bins).astype(x.dtype)           # [b, t]: b <= t
        below, above = (jnp.einsum("fbc,bt->ftc", x_skip, m,
                                   precision=lax.Precision.HIGHEST)
                        for m in (upto, upto.T))
        xd = jnp.sum(jnp.where(skip, x, zero), axis=1, keepdims=True)
        passed = (default_bin >= bins)[..., None]
        return below, above, above + jnp.where(passed, xd, zero)

    def scans(x, x_skip):
        def descending(v):
            return jnp.flip(jnp.cumsum(jnp.flip(v, axis=1), axis=1), axis=1)
        return jnp.cumsum(x_skip, axis=1), descending(x_skip), descending(x)

    return lax.platform_dependent(x, x_skip, tpu=mxu, default=scans)


def _pick(sel, v):
    """The one entry of v that the one-hot `sel` marks along the last
    axis, without a gather: adding zeros is exact."""
    return jnp.sum(jnp.where(sel, v, jnp.zeros((), v.dtype)), axis=-1)


def _best_bin(gain, ok, left, dbz, prefer_last: bool, min_gain_shift,
              threshold_of_bin: int = 0) -> _Cand:
    """The best split point of one pass, every feature at once.

    gain, ok: (F, B); left: the pass's (g, h, c) left sums at every split
    point; the threshold is the picked bin plus `threshold_of_bin`.  Equal
    gains keep the LAST bin of a descending scan (dir=-1) and the first of
    an ascending one."""
    B = gain.shape[-1]
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]
    gain = jnp.where(ok & (gain > min_gain_shift), gain, -jnp.inf)
    top = jnp.max(gain, axis=-1, keepdims=True)
    if prefer_last:
        pick = jnp.max(jnp.where(gain == top, bins, -1), axis=-1)
    else:
        pick = jnp.min(jnp.where(gain == top, bins, B), axis=-1)
    sel = bins == pick[:, None]
    left_g, left_h, left_c = left
    return _Cand(gain=top[:, 0], threshold=pick + threshold_of_bin, dbz=dbz,
                 left_g=_pick(sel, left_g), left_h=_pick(sel, left_h),
                 left_c=_pick(sel, left_c))


def _merge(best: _Cand, cand: _Cand) -> _Cand:
    """Later candidate replaces only on strictly greater gain."""
    take = cand.gain > best.gain
    return _Cand(*[jnp.where(take, cn, bn) for cn, bn in zip(cand, best)])


def per_feature_candidates(hist, total_g, total_h, total_cnt,
                           meta: FeatureMeta, params: SplitParams):
    """Per-feature best split candidates for one leaf.

    Returns (best: _Cand with (F,) arrays, total_g, total_h_eps, total_cnt,
    min_gain_shift).  `best.gain` is the raw gain (shift NOT yet subtracted);
    -inf marks unsplittable features.  The voting-parallel learner uses this
    to propose local top-k features (FindBestThresholds local pass,
    voting_parallel_tree_learner.cpp:255-300).
    """
    dtype = hist.dtype
    eps = jnp.asarray(kEpsilon, dtype)
    zero = jnp.zeros((), dtype)
    total_g = jnp.asarray(total_g, dtype)
    total_h_eps = jnp.asarray(total_h, dtype) + 2 * eps
    total_cnt = jnp.asarray(total_cnt, dtype)
    l1, l2 = params.lambda_l1, params.lambda_l2

    gain_shift = _leaf_split_gain(total_g, total_h_eps, l1, l2)
    min_gain_shift = gain_shift + params.min_gain_to_split

    bins = jnp.arange(hist.shape[1], dtype=jnp.int32)[None, :]
    num_bin = meta.num_bin[:, None]
    default_bin = meta.default_bin[:, None]
    valid = bins < num_bin
    not_default = bins != default_bin

    # the one scan of the histogram over its valid bins: what each of the
    # three passes has accumulated when it evaluates split point t.  Every
    # side a pass accumulates is a sum of the bins it holds, never a
    # difference of two larger sums.
    x = jnp.where(valid[..., None], hist, zero)
    below, above, above_all = _scan_sums(x, default_bin)

    def numerical_pass(acc, ascending: bool, skip_default: bool, dbz):
        """One FindBestThresholdSequence pass, vectorized over all
        features, from the sums `acc` (F, B, 3) its scan has accumulated
        when it evaluates split point t."""
        acc_g, acc_h, acc_c = acc[..., 0], acc[..., 1] + eps, acc[..., 2]
        rest_g = total_g - acc_g
        rest_h = total_h_eps - acc_h
        rest_c = total_cnt - acc_c
        if ascending:
            # dir = +1: accumulate the left side from bin 0 up; threshold = t
            t_ok = bins <= num_bin - 2
        else:
            # dir = -1: accumulate the right side from the top bin down;
            # split point t puts bins >= t on the right, threshold = t-1
            t_ok = (bins >= 1) & valid
        if skip_default:
            # the reference's `continue`: the sums at t = default_bin are
            # those of the next t, which the scan order prefers anyway
            t_ok = t_ok & not_default
        ok = (t_ok
              & (acc_c >= params.min_data_in_leaf)
              & (acc_h >= params.min_sum_hessian_in_leaf)
              & (rest_c >= params.min_data_in_leaf)
              & (rest_h >= params.min_sum_hessian_in_leaf))
        gain = (_leaf_split_gain(acc_g, acc_h, l1, l2)
                + _leaf_split_gain(rest_g, rest_h, l1, l2))
        left = (acc_g, acc_h, acc_c) if ascending else (rest_g, rest_h, rest_c)
        return _best_bin(gain, ok, left, dbz, not ascending, min_gain_shift,
                         threshold_of_bin=0 if ascending else -1)

    natural = numerical_pass(above_all, ascending=False, skip_default=False,
                             dbz=meta.default_bin)
    if params.use_missing:
        # zero_left: the descending scan less the default bin;
        # zero_right: the ascending scan less it
        best = numerical_pass(above, ascending=False, skip_default=True,
                              dbz=jnp.zeros_like(meta.default_bin))
        best = _merge(best, natural)
        best = _merge(best, numerical_pass(
            below, ascending=True, skip_default=True, dbz=meta.num_bin - 1))
    else:
        best = natural
    # the 'natural' pass with an edge default_bin duplicates a skip pass; the
    # reference guards those duplicates, we simply let _merge's strict >
    # keep the earlier pass.  Edge default bins are handled identically.

    # one-vs-rest categorical scan (feature_histogram.hpp:100-198): the left
    # side is the single category bin t, so it needs no sum over the bins;
    # ties keep the larger t (descending loop)
    g, h, c = x[..., 0], x[..., 1], x[..., 2]
    other_g = total_g - g
    other_h = total_h_eps - h - eps
    other_c = total_cnt - c
    ok = (valid
          & (c >= params.min_data_in_leaf)
          & (h >= params.min_sum_hessian_in_leaf)
          & (other_c >= params.min_data_in_leaf)
          & (other_h >= params.min_sum_hessian_in_leaf))
    gain = (_leaf_split_gain(other_g, other_h, l1, l2)
            + _leaf_split_gain(g, h + eps, l1, l2))
    cat = _best_bin(gain, ok, (g, h + eps, c), meta.default_bin, True,
                    min_gain_shift)
    best = _Cand(*[jnp.where(meta.is_categorical, cn, bn)
                   for cn, bn in zip(cat, best)])
    return best, total_g, total_h_eps, total_cnt, min_gain_shift


def find_best_split_impl(hist, total_g, total_h, total_cnt,
                         meta: FeatureMeta, feature_mask, params: SplitParams):
    """Best split for one leaf.

    Args:
      hist: (F, B, 3) float histogram [sum_grad, sum_hess, count].
      total_g / total_h / total_cnt: leaf totals (scalars).
      meta: FeatureMeta arrays.
      feature_mask: (F,) bool — feature_fraction sampling for this tree.
      params: SplitParams (static).

    Returns: packed (SPLIT_VEC_SIZE,) vector; gain=-inf when unsplittable.
    """
    best, total_g, total_h_eps, total_cnt, min_gain_shift = \
        per_feature_candidates(hist, total_g, total_h, total_cnt, meta, params)
    dtype = best.gain.dtype
    eps = jnp.asarray(kEpsilon, dtype)
    features = jnp.arange(best.gain.shape[0], dtype=jnp.int32)

    masked_gain = jnp.where(feature_mask, best.gain, -jnp.inf)
    f = jnp.argmax(masked_gain).astype(jnp.int32)  # ties -> smaller index
    won = features == f
    bgain = jnp.max(masked_gain)
    # runner-up: best gain over the OTHER features (split-audit margin)
    masked2 = jnp.where(won, -jnp.inf, masked_gain)
    f2 = jnp.argmax(masked2)
    g2 = jnp.max(masked2)
    lg, lh, lc = (_pick(won, best.left_g), _pick(won, best.left_h),
                  _pick(won, best.left_c))
    rg = total_g - lg
    rh = total_h_eps - lh
    rc = total_cnt - lc
    return jnp.stack([
        # keep a -inf gain truly -inf (the subtraction turns it into nan)
        jnp.where(jnp.isfinite(bgain), bgain - min_gain_shift, -jnp.inf),
        f.astype(dtype),
        _pick(won, best.threshold).astype(dtype),
        _pick(won, best.dbz).astype(dtype),
        _leaf_output(lg, lh, params.lambda_l1, params.lambda_l2),
        _leaf_output(rg, rh, params.lambda_l1, params.lambda_l2),
        lg,
        lh - eps,
        lc,
        rg,
        rh - eps,
        rc,
        jnp.any(won & meta.is_categorical).astype(dtype),
        jnp.where(jnp.isfinite(g2), f2, -1).astype(dtype),
        jnp.where(jnp.isfinite(g2), g2 - min_gain_shift,
                  jnp.asarray(0.0, dtype)),
    ])


@functools.partial(jax.jit, static_argnames=("params",))
def find_best_split(hist, total_g, total_h, total_cnt,
                    meta: FeatureMeta, feature_mask, params: SplitParams):
    """Jitted standalone wrapper around find_best_split_impl."""
    return find_best_split_impl(hist, total_g, total_h, total_cnt, meta,
                                feature_mask, params)


def depth_gated_best(hist, sums, meta, feature_mask, params: SplitParams,
                     max_depth: int, depth):
    """Best split of one leaf with the max_depth gate applied.

    `sums` is the (3,) [sum_grad, sum_hess, count] leaf total; a leaf at
    depth >= max_depth keeps its packed vector but has its gain forced to
    -inf so the frontier argmax can never pick it (tree.cpp max-depth
    check hoisted into the device program).
    """
    b = find_best_split_impl(hist, sums[0], sums[1], sums[2], meta,
                             feature_mask, params)
    if max_depth > 0:
        b = b.at[GAIN].set(jnp.where(depth < max_depth, b[GAIN], -jnp.inf))
    return b


def best_splits_vmapped(hists_k, sums_k, depths_k, meta, feature_mask,
                        params: SplitParams, max_depth: int, hist_view=None):
    """Packed best-split search vmapped over K leaves at once.

    The wave engine's frontier produces K = 2*W child histograms per
    pass; searching them as one vmapped program keeps the whole level's
    FindBestThreshold on-device: on the TPU two matmuls for the running
    sums of all K leaves and a handful of fusions over the (K, F, B)
    block (3.3 GB of traffic for the 98 MB block of 64 x 2,000 x 64,
    tests/test_obs_spans.py).  `hist_view`, when given, maps each leaf's
    raw group histogram (+ its sums) to the per-feature view (EFB gather
    / default-bin fix) inside the vmap.  Shared by ops/wave.py and
    ops/fused_iter.py.
    """
    def one(h, s, d):
        hv = hist_view(h, s) if hist_view is not None else h
        return depth_gated_best(hv, s, meta, feature_mask, params,
                                max_depth, d)
    return jax.vmap(one)(hists_k, sums_k, depths_k)
