"""Split-finder tests against a numpy oracle implementing
feature_histogram.hpp:78-387 literally (sequential scans)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import split_finder as sf
from lightgbm_tpu.ops.split_finder import (FeatureMeta, SplitParams,
                                           find_best_split,
                                           find_best_split_impl,
                                           per_feature_candidates, GAIN,
                                           FEATURE, THRESHOLD,
                                           DEFAULT_BIN_FOR_ZERO, LEFT_OUTPUT,
                                           RIGHT_OUTPUT, LEFT_SUM_G,
                                           LEFT_SUM_H, LEFT_COUNT,
                                           RIGHT_SUM_G, RIGHT_SUM_H,
                                           RIGHT_COUNT, IS_CAT,
                                           SECOND_FEATURE, SECOND_GAIN)

kEps = 1e-15


def oracle_gls(g, h, l1, l2):
    reg = max(abs(g) - l1, 0.0)
    return reg * reg / (h + l2)


def oracle_numerical(hist_g, hist_h, hist_c, num_bin, default_bin,
                     total_g, total_h, total_cnt, p: SplitParams):
    """Literal port of FindBestThresholdNumerical + 3 sequences."""
    total_h = total_h + 2 * kEps
    gain_shift = oracle_gls(total_g, total_h, p.lambda_l1, p.lambda_l2)
    min_gain_shift = gain_shift + p.min_gain_to_split
    best = {"gain": -np.inf}

    def sequence(dbz):
        nonlocal best
        dirn = 1 if dbz == num_bin - 1 else -1
        skip_default = not (0 < dbz < num_bin - 1)
        sg, sh, sc = 0.0, kEps, 0.0
        bb = {"gain": -np.inf}
        if dirn == -1:
            for t in range(num_bin - 1, 0, -1):
                if skip_default and t == default_bin:
                    continue
                sg += hist_g[t]; sh += hist_h[t]; sc += hist_c[t]
                if sc < p.min_data_in_leaf or sh < p.min_sum_hessian_in_leaf:
                    continue
                lc = total_cnt - sc
                if lc < p.min_data_in_leaf:
                    break
                lh = total_h - sh
                if lh < p.min_sum_hessian_in_leaf:
                    break
                lg = total_g - sg
                cur = oracle_gls(lg, lh, p.lambda_l1, p.lambda_l2) + \
                    oracle_gls(sg, sh, p.lambda_l1, p.lambda_l2)
                if cur <= min_gain_shift:
                    continue
                if cur > bb["gain"]:
                    bb = {"gain": cur, "thr": t - 1, "lg": lg, "lh": lh,
                          "lc": lc, "dbz": dbz}
        else:
            for t in range(0, num_bin - 1):
                if skip_default and t == default_bin:
                    continue
                sg += hist_g[t]; sh += hist_h[t]; sc += hist_c[t]
                if sc < p.min_data_in_leaf or sh < p.min_sum_hessian_in_leaf:
                    continue
                rc = total_cnt - sc
                if rc < p.min_data_in_leaf:
                    break
                rh = total_h - sh
                if rh < p.min_sum_hessian_in_leaf:
                    break
                rg = total_g - sg
                cur = oracle_gls(sg, sh, p.lambda_l1, p.lambda_l2) + \
                    oracle_gls(rg, rh, p.lambda_l1, p.lambda_l2)
                if cur <= min_gain_shift:
                    continue
                if cur > bb["gain"]:
                    bb = {"gain": cur, "thr": t, "lg": sg, "lh": sh,
                          "lc": sc, "dbz": dbz}
        if bb["gain"] > best["gain"]:
            best = bb

    if p.use_missing:
        sequence(0)
        if 0 < default_bin < num_bin - 1:
            sequence(default_bin)
        if num_bin > 2:
            sequence(num_bin - 1)
    else:
        sequence(default_bin)
    if best["gain"] == -np.inf:
        return None
    best["gain"] -= min_gain_shift
    return best


def run_case(rng, num_bin, default_bin, l1=0.0, l2=0.0, min_data=1,
             min_hess=1e-3, use_missing=True, min_gain=0.0):
    B = 16
    hist_g = np.zeros(B)
    hist_h = np.zeros(B)
    hist_c = np.zeros(B)
    hist_g[:num_bin] = rng.normal(size=num_bin) * 10
    hist_h[:num_bin] = rng.uniform(0.5, 2.0, size=num_bin) * 5
    hist_c[:num_bin] = rng.integers(1, 50, size=num_bin)
    tg, th, tc = hist_g.sum(), hist_h.sum(), hist_c.sum()
    params = SplitParams(l1, l2, min_gain, float(min_data), min_hess,
                         use_missing)
    meta = FeatureMeta(num_bin=jnp.asarray([num_bin], jnp.int32),
                       default_bin=jnp.asarray([default_bin], jnp.int32),
                       is_categorical=jnp.asarray([False]))
    hist = jnp.asarray(np.stack([hist_g, hist_h, hist_c], -1)[None],
                       jnp.float32)
    out = np.asarray(find_best_split(hist, tg, th, tc, meta,
                                     jnp.asarray([True]), params))
    oracle = oracle_numerical(hist_g, hist_h, hist_c, num_bin, default_bin,
                              tg, th, tc, params)
    return out, oracle


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("default_bin", [0, 3, 9])
def test_numerical_matches_oracle(seed, default_bin):
    rng = np.random.default_rng(seed)
    out, oracle = run_case(rng, num_bin=10, default_bin=default_bin)
    if oracle is None:
        assert out[GAIN] == -np.inf or out[GAIN] <= 0
        return
    assert out[GAIN] == pytest.approx(oracle["gain"], rel=2e-5)
    assert int(out[THRESHOLD]) == oracle["thr"]
    assert int(out[DEFAULT_BIN_FOR_ZERO]) == oracle["dbz"]
    assert out[LEFT_COUNT] == pytest.approx(oracle["lc"])


@pytest.mark.parametrize("seed", range(4))
def test_numerical_with_l1_l2_and_constraints(seed):
    rng = np.random.default_rng(100 + seed)
    out, oracle = run_case(rng, num_bin=12, default_bin=5, l1=2.0, l2=3.0,
                           min_data=30, min_hess=1.0)
    if oracle is None:
        assert not np.isfinite(out[GAIN]) or out[GAIN] <= 0
        return
    assert out[GAIN] == pytest.approx(oracle["gain"], rel=2e-5)
    assert int(out[THRESHOLD]) == oracle["thr"]


@pytest.mark.parametrize("seed", range(4))
def test_numerical_no_missing(seed):
    rng = np.random.default_rng(200 + seed)
    out, oracle = run_case(rng, num_bin=8, default_bin=4, use_missing=False)
    if oracle is None:
        return
    assert out[GAIN] == pytest.approx(oracle["gain"], rel=2e-5)
    assert int(out[THRESHOLD]) == oracle["thr"]


def test_categorical_one_vs_rest():
    # categorical: best split isolates the bin with extreme gradient
    B = 8
    hist_g = np.array([1.0, -30.0, 2.0, 1.5, 0, 0, 0, 0])
    hist_h = np.array([5.0, 10.0, 5.0, 5.0, 0, 0, 0, 0])
    hist_c = np.array([10, 20, 10, 10, 0, 0, 0, 0])
    params = SplitParams(0.0, 0.0, 0.0, 1.0, 1e-3, True)
    meta = FeatureMeta(num_bin=jnp.asarray([4], jnp.int32),
                       default_bin=jnp.asarray([0], jnp.int32),
                       is_categorical=jnp.asarray([True]))
    hist = jnp.asarray(np.stack([hist_g, hist_h, hist_c], -1)[None], jnp.float32)
    out = np.asarray(find_best_split(hist, hist_g.sum(), hist_h.sum(),
                                     hist_c.sum(), meta, jnp.asarray([True]),
                                     params))
    assert int(out[THRESHOLD]) == 1   # isolate category bin 1
    lg = hist_g[1]
    lh = hist_h[1]
    assert out[LEFT_OUTPUT] == pytest.approx(-lg / (lh + kEps), rel=1e-4)


def test_feature_tiebreak_prefers_smaller_index():
    # two identical features -> argmax picks feature 0
    hist_g = np.array([5.0, -5.0, 0, 0])
    hist_h = np.array([3.0, 3.0, 0, 0])
    hist_c = np.array([10, 10, 0, 0])
    one = np.stack([hist_g, hist_h, hist_c], -1)
    hist = jnp.asarray(np.stack([one, one]), jnp.float32)
    params = SplitParams(0.0, 0.0, 0.0, 1.0, 1e-3, True)
    meta = FeatureMeta(num_bin=jnp.asarray([2, 2], jnp.int32),
                       default_bin=jnp.asarray([0, 0], jnp.int32),
                       is_categorical=jnp.asarray([False, False]))
    out = np.asarray(find_best_split(hist, 0.0, 6.0, 20.0, meta,
                                     jnp.asarray([True, True]), params))
    assert int(out[FEATURE]) == 0


# ---------------------------------------------------------------------------
# What one scan of the bins and the passes derived from it can get wrong.
#
# `parent_*` below is the search's body as it stood before the histogram was
# read once (three masked copies, nine cumulative sums, gathers at the
# arg-max), kept here literally as a second reference beside the oracle.



def _parent_suffix_sum(x):
    return jnp.flip(jnp.cumsum(jnp.flip(x, axis=-1), axis=-1), axis=-1)


def _parent_argmax_prefer_last(x):
    n = x.shape[-1]
    return n - 1 - jnp.argmax(jnp.flip(x, axis=-1), axis=-1)


def _parent_numerical_pass(g, h, c, meta, params, total_g, total_h_eps,
                           total_cnt, min_gain_shift, mode):
    F, B = g.shape
    bins = jnp.arange(B, dtype=jnp.int32)
    valid = bins[None, :] < meta.num_bin[:, None]
    if mode in ("zero_left", "zero_right"):
        keep = valid & (bins[None, :] != meta.default_bin[:, None])
    else:
        keep = valid
    gk = jnp.where(keep, g, 0.0)
    hk = jnp.where(keep, h, 0.0)
    ck = jnp.where(keep, c, 0.0)
    eps = jnp.asarray(sf.kEpsilon, g.dtype)
    if mode != "zero_right":
        right_g = _parent_suffix_sum(gk)
        right_h = _parent_suffix_sum(hk) + eps
        right_c = _parent_suffix_sum(ck)
        left_g = total_g - right_g
        left_h = total_h_eps - right_h
        left_c = total_cnt - right_c
        t_ok = (bins[None, :] >= 1) & valid
        threshold = bins[None, :] - 1
        prefer_last = True
    else:
        left_g = jnp.cumsum(gk, axis=-1)
        left_h = jnp.cumsum(hk, axis=-1) + eps
        left_c = jnp.cumsum(ck, axis=-1)
        right_g = total_g - left_g
        right_h = total_h_eps - left_h
        right_c = total_cnt - left_c
        t_ok = (bins[None, :] <= meta.num_bin[:, None] - 2) & valid
        threshold = jnp.broadcast_to(bins[None, :], (F, B))
        prefer_last = False
    ok = (t_ok
          & (right_c >= params.min_data_in_leaf)
          & (right_h >= params.min_sum_hessian_in_leaf)
          & (left_c >= params.min_data_in_leaf)
          & (left_h >= params.min_sum_hessian_in_leaf))
    gain = (sf._leaf_split_gain(left_g, left_h, params.lambda_l1,
                                params.lambda_l2)
            + sf._leaf_split_gain(right_g, right_h, params.lambda_l1,
                                  params.lambda_l2))
    ok = ok & (gain > min_gain_shift)
    gain = jnp.where(ok, gain, -jnp.inf)
    pick = (_parent_argmax_prefer_last(gain) if prefer_last
            else jnp.argmax(gain, axis=-1))
    fidx = jnp.arange(F)
    if mode == "zero_left":
        dbz = jnp.zeros(F, jnp.int32)
    elif mode == "natural":
        dbz = meta.default_bin
    else:
        dbz = meta.num_bin - 1
    return sf._Cand(gain=gain[fidx, pick],
                    threshold=threshold[fidx, pick].astype(jnp.int32),
                    dbz=dbz, left_g=left_g[fidx, pick],
                    left_h=left_h[fidx, pick], left_c=left_c[fidx, pick])


def _parent_categorical_pass(g, h, c, meta, params, total_g, total_h_eps,
                             total_cnt, min_gain_shift):
    F, B = g.shape
    bins = jnp.arange(B, dtype=jnp.int32)
    valid = bins[None, :] < meta.num_bin[:, None]
    eps = jnp.asarray(sf.kEpsilon, g.dtype)
    other_c = total_cnt - c
    other_h = total_h_eps - h - eps
    other_g = total_g - g
    ok = (valid
          & (c >= params.min_data_in_leaf)
          & (h >= params.min_sum_hessian_in_leaf)
          & (other_c >= params.min_data_in_leaf)
          & (other_h >= params.min_sum_hessian_in_leaf))
    gain = (sf._leaf_split_gain(other_g, other_h, params.lambda_l1,
                                params.lambda_l2)
            + sf._leaf_split_gain(g, h + eps, params.lambda_l1,
                                  params.lambda_l2))
    ok = ok & (gain > min_gain_shift)
    gain = jnp.where(ok, gain, -jnp.inf)
    pick = _parent_argmax_prefer_last(gain)
    fidx = jnp.arange(F)
    return sf._Cand(gain=gain[fidx, pick], threshold=pick.astype(jnp.int32),
                    dbz=meta.default_bin, left_g=g[fidx, pick],
                    left_h=h[fidx, pick] + eps, left_c=c[fidx, pick])


def parent_per_feature_candidates(hist, total_g, total_h, total_cnt, meta,
                                  params):
    g, h, c = hist[..., 0], hist[..., 1], hist[..., 2]
    dtype = g.dtype
    eps = jnp.asarray(sf.kEpsilon, dtype)
    total_g = jnp.asarray(total_g, dtype)
    total_h_eps = jnp.asarray(total_h, dtype) + 2 * eps
    total_cnt = jnp.asarray(total_cnt, dtype)
    gain_shift = sf._leaf_split_gain(total_g, total_h_eps, params.lambda_l1,
                                     params.lambda_l2)
    min_gain_shift = gain_shift + params.min_gain_to_split
    args = (g, h, c, meta, params, total_g, total_h_eps, total_cnt,
            min_gain_shift)
    if params.use_missing:
        best = _parent_numerical_pass(*args, mode="zero_left")
        best = sf._merge(best, _parent_numerical_pass(*args, mode="natural"))
        best = sf._merge(best,
                         _parent_numerical_pass(*args, mode="zero_right"))
    else:
        best = _parent_numerical_pass(*args, mode="natural")
    cat = _parent_categorical_pass(*args)
    best = sf._Cand(*[jnp.where(meta.is_categorical, cn, bn)
                      for cn, bn in zip(cat, best)])
    return best, total_g, total_h_eps, total_cnt, min_gain_shift


def parent_find_best_split(hist, total_g, total_h, total_cnt, meta,
                           feature_mask, params):
    best, total_g, total_h_eps, total_cnt, min_gain_shift = \
        parent_per_feature_candidates(hist, total_g, total_h, total_cnt,
                                      meta, params)
    dtype = best.gain.dtype
    eps = jnp.asarray(sf.kEpsilon, dtype)
    masked_gain = jnp.where(feature_mask, best.gain, -jnp.inf)
    f = jnp.argmax(masked_gain)
    bgain = masked_gain[f]
    masked2 = masked_gain.at[f].set(-jnp.inf)
    f2 = jnp.argmax(masked2)
    g2 = masked2[f2]
    lg, lh, lc = best.left_g[f], best.left_h[f], best.left_c[f]
    rg = total_g - lg
    rh = total_h_eps - lh
    rc = total_cnt - lc
    out = jnp.stack([
        bgain - min_gain_shift, f.astype(dtype),
        best.threshold[f].astype(dtype), best.dbz[f].astype(dtype),
        sf._leaf_output(lg, lh, params.lambda_l1, params.lambda_l2),
        sf._leaf_output(rg, rh, params.lambda_l1, params.lambda_l2),
        lg, lh - eps, lc, rg, rh - eps, rc,
        meta.is_categorical[f].astype(dtype),
        jnp.where(jnp.isfinite(g2), f2, -1).astype(dtype),
        jnp.where(jnp.isfinite(g2), g2 - min_gain_shift,
                  jnp.asarray(0.0, dtype))])
    return out.at[GAIN].set(jnp.where(jnp.isfinite(bgain),
                                      bgain - min_gain_shift, -jnp.inf))


def oracle_categorical(hist_g, hist_h, hist_c, num_bin, total_g, total_h,
                       total_cnt, p):
    """One-vs-rest scan, descending over the category bins
    (feature_histogram.hpp:100-198)."""
    total_h = total_h + 2 * kEps
    min_gain_shift = oracle_gls(total_g, total_h, p.lambda_l1,
                                p.lambda_l2) + p.min_gain_to_split
    best = None
    for t in range(num_bin - 1, -1, -1):
        if hist_c[t] < p.min_data_in_leaf \
                or hist_h[t] < p.min_sum_hessian_in_leaf:
            continue
        oc = total_cnt - hist_c[t]
        oh = total_h - hist_h[t] - kEps
        if oc < p.min_data_in_leaf or oh < p.min_sum_hessian_in_leaf:
            continue
        cur = oracle_gls(total_g - hist_g[t], oh, p.lambda_l1, p.lambda_l2) \
            + oracle_gls(hist_g[t], hist_h[t] + kEps, p.lambda_l1,
                         p.lambda_l2)
        if cur <= min_gain_shift:
            continue
        if best is None or cur > best["gain"]:
            best = {"gain": cur, "thr": t, "lc": hist_c[t]}
    if best is not None:
        best["gain"] -= min_gain_shift
    return best


def _mixed_features(rng, num_bins, default_pos, B=64, garbage=1e6):
    """(F, B, 3) float64 histograms of features with their own `num_bin`,
    the default bin first, in the middle or last, and `garbage` in the
    bins a feature does not have: whatever is masked must stay out of the
    running sums and the totals."""
    F = len(num_bins)
    hist = np.full((F, B, 3), garbage)
    default = np.zeros(F, np.int64)
    for f, nb in enumerate(num_bins):
        hist[f, :nb, 0] = rng.normal(size=nb) * 10
        hist[f, :nb, 1] = rng.uniform(0.5, 2.0, size=nb) * 5
        hist[f, :nb, 2] = rng.integers(1, 50, size=nb)
        default[f] = {"first": 0, "middle": nb // 2, "last": nb - 1}[
            default_pos if isinstance(default_pos, str)
            else default_pos[f % len(default_pos)]]
    return hist, default


def _candidates64(hist, totals, num_bins, default, is_cat, params):
    """per_feature_candidates in float64 (the oracle's precision, so that
    only an exact tie is a tie), as numpy arrays, gain less its shift."""
    with jax.enable_x64(True):
        meta = FeatureMeta(num_bin=jnp.asarray(num_bins, jnp.int32),
                           default_bin=jnp.asarray(default, jnp.int32),
                           is_categorical=jnp.asarray(is_cat))
        best, _, _, _, shift = jax.jit(
            lambda h, t: per_feature_candidates(h, t[0], t[1], t[2], meta,
                                                params))(
            jnp.asarray(hist, jnp.float64), jnp.asarray(totals, jnp.float64))
        return sf._Cand(*[np.asarray(v) for v in best])._replace(
            gain=np.asarray(best.gain) - float(shift))


def _assert_matches_oracle(cand, f, oracle, num_bin=None, default=None):
    if oracle is None and num_bin == 2 and default == 1:
        # the reference runs no ascending pass on two bins and no natural
        # one with the default bin last; the natural pass here (as in the
        # parent's body) offers the one split there is
        if np.isfinite(cand.gain[f]):
            assert (int(cand.threshold[f]), int(cand.dbz[f])) == (0, 1)
        return
    if oracle is None:
        assert cand.gain[f] == -np.inf
        return
    assert cand.gain[f] == pytest.approx(oracle["gain"], rel=1e-9)
    assert int(cand.threshold[f]) == oracle["thr"]
    assert cand.left_c[f] == pytest.approx(oracle["lc"], rel=1e-9)
    if "dbz" in oracle and int(cand.dbz[f]) != oracle["dbz"]:
        # two passes can offer one partition (the natural scan at t + 1 and
        # the ascending one at t < default_bin): equal gains but for the
        # last bit, which decided in the parent's body too.  The default
        # bin's rows must go to the same side under either label.
        thr = oracle["thr"]
        assert (int(cand.dbz[f]) <= thr) == (oracle["dbz"] <= thr)
        assert {int(cand.dbz[f]), oracle["dbz"]} == {default, num_bin - 1}


NUM_BINS = [2, 3, 4, 5, 9, 16, 17, 33, 48, 63, 64, 2, 64, 7]


@pytest.mark.parametrize("use_missing", [True, False])
@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("default_pos", ["first", "middle", "last"])
def test_default_bin_anywhere_with_mixed_num_bin(default_pos, seed,
                                                 use_missing):
    rng = np.random.default_rng(300 + seed)
    hist, default = _mixed_features(rng, NUM_BINS, default_pos)
    # every feature's bins hold the same leaf: scale to common totals
    totals = [hist[0, :2, k].sum() for k in range(3)]
    for f, nb in enumerate(NUM_BINS):
        for k in range(3):
            hist[f, :nb, k] *= totals[k] / hist[f, :nb, k].sum()
    params = SplitParams(0.5, 1.0, 0.0, 2.0, 1.0, use_missing)
    cand = _candidates64(hist, totals, NUM_BINS, default,
                         [False] * len(NUM_BINS), params)
    for f, nb in enumerate(NUM_BINS):
        oracle = oracle_numerical(hist[f, :, 0], hist[f, :, 1], hist[f, :, 2],
                                  nb, int(default[f]), *totals, params)
        _assert_matches_oracle(cand, f, oracle, nb, int(default[f]))


@pytest.mark.parametrize("seed", range(3))
def test_categorical_mixed_with_numerical(seed):
    rng = np.random.default_rng(400 + seed)
    num_bins = [12, 64, 5, 33, 2, 20]
    is_cat = [True, False, True, False, False, True]
    hist, default = _mixed_features(rng, num_bins,
                                    ("middle", "last", "first"))
    totals = [hist[0, :12, k].sum() for k in range(3)]
    for f, nb in enumerate(num_bins):
        for k in range(3):
            hist[f, :nb, k] *= totals[k] / hist[f, :nb, k].sum()
    params = SplitParams(0.0, 0.5, 0.0, 3.0, 1.0, True)
    cand = _candidates64(hist, totals, num_bins, default, is_cat, params)
    for f, nb in enumerate(num_bins):
        col = (hist[f, :, 0], hist[f, :, 1], hist[f, :, 2])
        if is_cat[f]:
            oracle = oracle_categorical(*col, nb, *totals, params)
        else:
            oracle = oracle_numerical(*col, nb, int(default[f]), *totals,
                                      params)
        _assert_matches_oracle(cand, f, oracle, nb, int(default[f]))
        if is_cat[f] and oracle is not None:
            assert int(cand.dbz[f]) == default[f]


def _bins(rows, B=8):
    """(1, B, 3) histogram from [(g, h, c)] a bin."""
    hist = np.zeros((1, B, 3))
    hist[0, :len(rows)] = rows
    return hist


EMPTY = (0.0, 0.0, 0.0)
TIES = {
    # dir=-1 inside a pass: bin 1 is empty, so split points 1 and 2 hold
    # the same sums; the descending scan keeps the larger threshold
    "descending_keeps_larger_threshold": dict(
        rows=[(6.0, 2.0, 10), EMPTY, (-6.0, 2.0, 10)], default=0,
        use_missing=False, thr=1, dbz=0),
    # dir=+1: only the ascending pass can put the default bin (1) right of
    # a threshold above it; bin 3 is empty, so thresholds 2 and 3 tie and
    # the ascending scan keeps the smaller
    "ascending_keeps_smaller_threshold": dict(
        rows=[(4.0, 1.0, 10), (-8.0, 1.0, 10), (4.0, 1.0, 10), EMPTY,
              (-4.0, 1.0, 10)], default=1, use_missing=True, thr=2, dbz=4),
    # between passes: an empty default bin in the middle makes zero_left
    # and natural the same scan; the earlier pass (dbz=0) stays
    "earlier_pass_wins": dict(
        rows=[(5.0, 2.0, 10), EMPTY, (-5.0, 2.0, 10), (1.0, 2.0, 10)],
        default=1, use_missing=True, thr=1, dbz=0),
}


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("case", sorted(TIES))
def test_exact_gain_ties_follow_the_scan_order(case, dtype):
    spec = TIES[case]
    hist = _bins(spec["rows"])
    nb = len(spec["rows"])
    totals = hist[0].sum(axis=0)
    params = SplitParams(0.0, 0.0, 0.0, 1.0, 1e-3, spec["use_missing"])
    oracle = oracle_numerical(hist[0, :, 0], hist[0, :, 1], hist[0, :, 2],
                              nb, spec["default"], *totals, params)
    assert (oracle["thr"], oracle["dbz"]) == (spec["thr"], spec["dbz"])
    with jax.enable_x64(dtype == "float64"):
        meta = FeatureMeta(num_bin=jnp.asarray([nb], jnp.int32),
                           default_bin=jnp.asarray([spec["default"]],
                                                   jnp.int32),
                           is_categorical=jnp.asarray([False]))
        out = np.asarray(find_best_split(
            jnp.asarray(hist, dtype), *totals, meta, jnp.asarray([True]),
            params))
    assert out.dtype == np.dtype(dtype)
    assert int(out[THRESHOLD]) == spec["thr"]
    assert int(out[DEFAULT_BIN_FOR_ZERO]) == spec["dbz"]
    assert out[GAIN] == pytest.approx(oracle["gain"], rel=1e-5)
    assert out[LEFT_COUNT] == oracle["lc"]


@pytest.mark.parametrize("mask,first,second", [
    ([True, True, True], 0, 1),        # equal gains: the smaller index
    ([False, True, True], 1, 2),       # ... among the sampled features
    ([False, False, True], 2, -1),     # no runner-up left
])
def test_equal_features_tie_to_the_smaller_index(mask, first, second):
    one = _bins([(5.0, 3.0, 10), (-5.0, 3.0, 10), (2.0, 1.0, 5)])[0]
    hist = jnp.asarray(np.stack([one, one, one]), jnp.float32)
    meta = FeatureMeta(num_bin=jnp.asarray([3, 3, 3], jnp.int32),
                       default_bin=jnp.asarray([0, 0, 0], jnp.int32),
                       is_categorical=jnp.asarray([False] * 3))
    params = SplitParams(0.0, 0.0, 0.0, 1.0, 1e-3, True)
    out = np.asarray(find_best_split(hist, 2.0, 7.0, 25.0, meta,
                                     jnp.asarray(mask), params))
    assert int(out[FEATURE]) == first
    assert int(out[SECOND_FEATURE]) == second
    assert out[SECOND_GAIN] == (out[GAIN] if second >= 0 else 0.0)


@pytest.mark.parametrize("splittable", [(False, True), (True, False),
                                        (False, False)])
def test_feature_with_no_valid_split_beside_one_with(splittable):
    """All rows in one bin: no split point leaves min_data on both sides.
    Such a feature must lose to any that splits, and with no such feature
    the gain is -inf, not nan."""
    lone = _bins([EMPTY, (3.0, 6.0, 30), EMPTY])[0]
    good = _bins([(9.0, 3.0, 15), (-6.0, 3.0, 15)])[0]
    hist = jnp.asarray(np.stack([good if s else lone for s in splittable]),
                       jnp.float32)
    meta = FeatureMeta(num_bin=jnp.asarray([3, 3], jnp.int32),
                       default_bin=jnp.asarray([1, 1], jnp.int32),
                       is_categorical=jnp.asarray([False, False]))
    params = SplitParams(0.0, 0.0, 0.0, 1.0, 1e-3, True)
    out = np.asarray(find_best_split(hist, 3.0, 6.0, 30.0, meta,
                                     jnp.asarray([True, True]), params))
    assert not np.isnan(out[GAIN])
    if any(splittable):
        assert int(out[FEATURE]) == splittable.index(True)
        assert out[GAIN] > 0 and int(out[THRESHOLD]) == 0
        assert out[LEFT_COUNT] == 15 and out[RIGHT_COUNT] == 15
    else:
        assert out[GAIN] == -np.inf
    assert int(out[SECOND_FEATURE]) == -1 and out[SECOND_GAIN] == 0.0


@pytest.mark.parametrize("sums", ["default", "mxu"])
def test_counts_below_2_24_come_out_exact_to_the_bit(sums, monkeypatch):
    """1,200,000 rows a bin up to 2^24 - 1 in all: every leaf's count is
    read from the running sums, so float32 must carry it exactly (on the
    TPU the same contraction runs on the MXU at HIGHEST precision: three
    exact bfloat16 terms; a chip run of PR 31 read it exact too)."""
    total = 2 ** 24 - 1
    counts = np.zeros(64, np.float32)
    counts[:13] = 1_200_000
    counts[13] = total - 13 * 1_200_000
    x = np.zeros((2, 64, 3), np.float32)
    x[0, :, 2] = counts
    x[1, :, 2] = counts[::-1]
    x[:, :, 1] = x[:, :, 2] * 0.25
    x[:, :, 0] = x[:, :, 2] * np.where(np.arange(64) % 2, 0.5, -0.5)
    if sums == "mxu":
        # the TPU's branch, on the CPU: the same contractions as a plain dot
        monkeypatch.setattr(
            jax.lax, "platform_dependent",
            lambda *args, tpu, default: tpu(*args))
    default_bin = jnp.asarray([[0], [63]], jnp.int32)
    below, above, above_all = (np.asarray(v) for v in jax.jit(
        lambda v: sf._scan_sums(v, default_bin))(jnp.asarray(x)))
    assert below.dtype == above.dtype == above_all.dtype == np.float32
    exact = x.astype(np.float64)
    skip = exact.copy()
    skip[0, 0] = skip[1, 63] = 0.0
    np.testing.assert_array_equal(below, np.cumsum(skip, axis=1))
    np.testing.assert_array_equal(
        above, np.cumsum(skip[:, ::-1], axis=1)[:, ::-1])
    np.testing.assert_array_equal(
        above_all, np.cumsum(exact[:, ::-1], axis=1)[:, ::-1])
    assert above_all[0, 0, 2] == above_all[1, 0, 2] == total
    # and through the search: both sides' counts are whole and add up
    meta = FeatureMeta(num_bin=jnp.asarray([64, 64], jnp.int32),
                       default_bin=jnp.asarray([0, 63], jnp.int32),
                       is_categorical=jnp.asarray([False, False]))
    params = SplitParams(0.0, 0.0, 0.0, 1.0, 1e-3, True)
    out = np.asarray(jax.jit(
        lambda h: find_best_split_impl(h, h[0, :, 0].sum(), total * 0.25,
                                       float(total), meta,
                                       jnp.asarray([True, True]), params))(
        jnp.asarray(x)))
    thr, f = int(out[THRESHOLD]), int(out[FEATURE])
    assert out[LEFT_COUNT] == x[f, :thr + 1, 2].astype(np.float64).sum()
    assert out[LEFT_COUNT] + out[RIGHT_COUNT] == total


PARITY_SHAPE = (8, 33, 64, 3)


def _random_leaves(seed, dtype):
    """Eight leaves' histograms over 33 features of 2-64 bins, default bin
    first, middle or last; each feature's bins hold the leaf's own rows."""
    rng = np.random.default_rng(1000 + seed)
    K, F, B, _ = PARITY_SHAPE
    num_bin = rng.integers(2, B + 1, size=F)
    default = np.where(rng.integers(0, 3, size=F) == 0, 0,
                       np.where(rng.integers(0, 2, size=F) == 0,
                                num_bin // 2, num_bin - 1))
    hist = np.zeros(PARITY_SHAPE)
    n = rng.integers(200, 20000, size=K)
    for f in range(F):
        share = rng.dirichlet(np.full(num_bin[f], 2.0), size=K)
        c = np.floor(share * n[:, None])
        c[:, 0] += n - c.sum(axis=1)
        hist[:, f, :num_bin[f], 2] = c
        hist[:, f, :num_bin[f], 1] = c * rng.uniform(0.05, 0.25,
                                                     size=c.shape)
        hist[:, f, :num_bin[f], 0] = rng.normal(size=c.shape) * np.sqrt(c)
    sums = np.stack([hist[:, 0, :, 0].sum(-1), hist[:, 0, :, 1].sum(-1), n],
                    axis=-1)
    meta = FeatureMeta(num_bin=jnp.asarray(num_bin, jnp.int32),
                       default_bin=jnp.asarray(default, jnp.int32),
                       is_categorical=jnp.asarray(rng.random(F) < 0.1))
    return (jnp.asarray(hist, dtype), jnp.asarray(sums, dtype), meta,
            jnp.asarray(rng.random(F) < 0.9))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("block", range(10))
def test_one_scan_agrees_with_the_parents_body(block, dtype):
    """200 seeded leaves-of-eight: wherever the parent's best gain leads
    its runner-up by more than 1e-6 relative, feature, threshold and
    default_bin_for_zero are the parent's; the sums agree to 1e-6 of the
    leaf's own (sums are re-associated, nothing else).  Where the two
    differ at all, it is between candidates whose gains tie to 1e-6."""
    params = SplitParams(0.1, 1.0, 0.0, 20.0, 1.0, True)
    differ = 0
    # a gain is a difference of squares of sums, less the parent's: in
    # float32 the re-associated sums move it in the fourth digit
    tie = 1e-3 if dtype == "float32" else 1e-6
    with jax.enable_x64(dtype == "float64"):
        def both(hist, sums, meta, mask):
            def one(fn):
                return jax.vmap(lambda h, s: fn(h, s[0], s[1], s[2], meta,
                                                mask, params))(hist, sums)
            return one(find_best_split_impl), one(parent_find_best_split)
        both = jax.jit(both)
        for seed in range(20 * block, 20 * block + 20):
            hist, sums, meta, mask = _random_leaves(seed, dtype)
            new, old = (np.asarray(v, np.float64)
                        for v in both(hist, sums, meta, mask))
            # a re-associated sum is off by rounding of what was added
            scale = np.abs(np.asarray(hist, np.float64)).sum(axis=2).max(
                axis=1)
            for k in range(PARITY_SHAPE[0]):
                a, b = new[k], old[k]
                if not np.isfinite(b[GAIN]):
                    assert a[GAIN] == b[GAIN]
                    continue
                same = all(a[i] == b[i] for i in (
                    FEATURE, THRESHOLD, DEFAULT_BIN_FOR_ZERO, IS_CAT))
                if not same:
                    differ += 1
                    assert abs(a[GAIN] - b[GAIN]) <= tie * abs(b[GAIN]), \
                        (seed, k, a, b)
                    continue
                assert a[GAIN] == pytest.approx(b[GAIN], rel=tie)
                for i, s in ((LEFT_SUM_G, 0), (RIGHT_SUM_G, 0),
                             (LEFT_SUM_H, 1), (RIGHT_SUM_H, 1),
                             (LEFT_COUNT, 2), (RIGHT_COUNT, 2)):
                    assert abs(a[i] - b[i]) <= 1e-6 * max(scale[k, s], 1.0), \
                        (seed, k, i, a[i], b[i])
                assert a[LEFT_COUNT] == b[LEFT_COUNT]
                if b[GAIN] - b[SECOND_GAIN] > 1e-6 * abs(b[GAIN]) \
                        and a[SECOND_FEATURE] != b[SECOND_FEATURE]:
                    assert a[SECOND_GAIN] == pytest.approx(b[SECOND_GAIN],
                                                           rel=1e-5)
    assert differ <= 2, differ
