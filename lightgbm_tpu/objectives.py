"""Objective functions — gradients/hessians on device.

Parity targets: src/objective/regression_objective.hpp,
binary_objective.hpp, multiclass_objective.hpp, rank_objective.hpp and the
factory in src/objective/objective_function.cpp:9-56.  Elementwise objectives
are jnp expressions (fused by XLA into the boosting step); lambdarank runs
the reference's per-query pairwise semantics fully on device as a jitted
vmap over padded query segments (the numpy per-query path is kept as the
test oracle, get_gradients_host).

Multi-class score layout matches the reference: column-major per class, i.e.
``score[k * num_data + i]`` (multiclass_objective.hpp:60-75); arrays here are
shaped (num_class, num_data) with the same meaning.
"""
from __future__ import annotations

import copy
import functools
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .io.metadata import Metadata
from .utils.config import Config
from .utils.log import Log

kEpsilon = 1e-15


def _apply_weights(g, h, w):
    if w is None:
        return g, h
    return g * w, h * w


class ObjectiveFunction:
    name = "base"

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = None if metadata.label is None else jnp.asarray(metadata.label)
        self.weights = None if metadata.weights is None else jnp.asarray(metadata.weights)

    def get_gradients(self, score):
        raise NotImplementedError

    def split_device_state(self):
        """(arrays, rebind): every device array this objective holds
        (labels, weights, per-row tables), and a function that returns a
        shallow copy of the objective bound to replacement arrays.

        The fused iteration (ops/fused_iter.py) passes ``arrays`` as
        program ARGUMENTS and calls ``rebind(tracers).get_gradients``
        inside the trace, so no dataset-sized array is baked into the
        compiled program as a literal."""
        leaves, treedef = jax.tree_util.tree_flatten(vars(self))
        on_device = [isinstance(v, jax.Array) for v in leaves]

        def rebind(arrays):
            it = iter(arrays)
            merged = [next(it) if d else v
                      for v, d in zip(leaves, on_device)]
            clone = copy.copy(self)
            vars(clone).update(jax.tree_util.tree_unflatten(treedef, merged))
            return clone

        return [v for v, d in zip(leaves, on_device) if d], rebind

    def convert_output(self, x):
        return x

    def is_constant_hessian(self) -> bool:
        return False

    def boost_from_average(self) -> bool:
        return False

    def skip_empty_class(self) -> bool:
        return False

    def num_tree_per_iteration(self) -> int:
        return 1

    def num_predict_one_row(self) -> int:
        return 1

    def to_string(self) -> str:
        return self.name

    def get_name(self) -> str:
        return self.name


class RegressionL2loss(ObjectiveFunction):
    """regression_objective.hpp:11-73: g = score - label, h = 1."""
    name = "regression"

    def get_gradients(self, score):
        g = score - self.label
        h = jnp.ones_like(score)
        return _apply_weights(g, h, self.weights)

    def is_constant_hessian(self) -> bool:
        return self.weights is None

    def boost_from_average(self) -> bool:
        return True


def _approx_hessian_with_gaussian(score, label, g, eta, w=1.0):
    """Common::ApproximateHessianWithGaussian (utils/common.h:486-495)."""
    diff = score - label
    x = jnp.abs(diff)
    a = 2.0 * jnp.abs(g) * w
    c = jnp.maximum((jnp.abs(score) + jnp.abs(label)) * eta, 1.0e-10)
    return w * jnp.exp(-x * x / (2.0 * c * c)) * a / (c * jnp.sqrt(2 * jnp.pi))


class RegressionL1loss(ObjectiveFunction):
    """regression_objective.hpp:78-146: sign gradient + gaussian-approx hessian."""
    name = "regression_l1"

    def __init__(self, config: Config):
        self.eta = float(config.gaussian_eta)

    def get_gradients(self, score):
        diff = score - self.label
        w = self.weights if self.weights is not None else 1.0
        g = jnp.where(diff >= 0.0, 1.0, -1.0) * w
        h = _approx_hessian_with_gaussian(score, self.label, g, self.eta,
                                          w if self.weights is not None else 1.0)
        return g, h

    def boost_from_average(self) -> bool:
        return True


class RegressionHuberLoss(ObjectiveFunction):
    """regression_objective.hpp:149-230."""
    name = "huber"

    def __init__(self, config: Config):
        self.delta = float(config.huber_delta)
        self.eta = float(config.gaussian_eta)

    def get_gradients(self, score):
        diff = score - self.label
        w = self.weights if self.weights is not None else 1.0
        small = jnp.abs(diff) <= self.delta
        g = jnp.where(small, diff, jnp.where(diff >= 0.0, self.delta, -self.delta)) * w
        h_large = _approx_hessian_with_gaussian(
            score, self.label, g, self.eta,
            w if self.weights is not None else 1.0)
        h = jnp.where(small, jnp.ones_like(score) * w, h_large)
        return g, h

    def boost_from_average(self) -> bool:
        return True


class RegressionFairLoss(ObjectiveFunction):
    """regression_objective.hpp:235-296."""
    name = "fair"

    def __init__(self, config: Config):
        self.c = float(config.fair_c)

    def get_gradients(self, score):
        x = score - self.label
        g = self.c * x / (jnp.abs(x) + self.c)
        h = self.c * self.c / ((jnp.abs(x) + self.c) ** 2)
        return _apply_weights(g, h, self.weights)

    def boost_from_average(self) -> bool:
        return True


class RegressionPoissonLoss(ObjectiveFunction):
    """regression_objective.hpp:299-355: this line's Poisson works on the raw
    score with h = score + max_delta_step."""
    name = "poisson"

    def __init__(self, config: Config):
        self.max_delta_step = float(config.poisson_max_delta_step)

    def get_gradients(self, score):
        g = score - self.label
        h = score + self.max_delta_step
        return _apply_weights(g, h, self.weights)

    def boost_from_average(self) -> bool:
        return True


class BinaryLogloss(ObjectiveFunction):
    """binary_objective.hpp:13-154 incl. is_unbalance label weights and
    scale_pos_weight."""
    name = "binary"

    def __init__(self, config: Optional[Config] = None, is_pos=None,
                 sigmoid: Optional[float] = None,
                 scale_pos_weight: Optional[float] = None,
                 is_unbalance: Optional[bool] = None):
        if config is not None:
            self.sigmoid = float(config.sigmoid)
            self.scale_pos_weight = float(config.scale_pos_weight)
            self.is_unbalance = bool(config.is_unbalance)
        else:
            self.sigmoid = 1.0 if sigmoid is None else float(sigmoid)
            self.scale_pos_weight = 1.0 if scale_pos_weight is None else scale_pos_weight
            self.is_unbalance = bool(is_unbalance)
        if self.sigmoid <= 0.0:
            Log.fatal("Sigmoid parameter %f should be greater than zero", self.sigmoid)
        self._is_pos = is_pos if is_pos is not None else (lambda label: label > 0)

    def init(self, metadata: Metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label)
        pos_mask = self._is_pos(lab)
        cnt_pos = int(pos_mask.sum())
        cnt_neg = int(num_data - cnt_pos)
        self.trainable = not (cnt_pos == 0 or cnt_neg == 0)
        if not self.trainable:
            Log.warning("Only contain one class.")
        lw = [1.0, 1.0]
        if self.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                lw[0] = cnt_pos / cnt_neg
            else:
                lw[1] = cnt_neg / cnt_pos
        lw[1] *= self.scale_pos_weight
        Log.info("Number of positive: %d, number of negative: %d", cnt_pos, cnt_neg)
        self.sign = jnp.asarray(np.where(pos_mask, 1.0, -1.0), jnp.float32)
        self.label_weight = jnp.asarray(np.where(pos_mask, lw[1], lw[0]), jnp.float32)

    def get_gradients(self, score):
        if not self.trainable:
            z = jnp.zeros(self.num_data, score.dtype)
            return z, z
        # binary_objective.hpp:94-97
        response = -self.sign * self.sigmoid / (1.0 + jnp.exp(self.sign * self.sigmoid * score))
        abs_resp = jnp.abs(response)
        g = response * self.label_weight
        h = abs_resp * (self.sigmoid - abs_resp) * self.label_weight
        if self.weights is not None:
            g = g * self.weights
            h = h * self.weights
        return g, h

    def convert_output(self, x):
        return 1.0 / (1.0 + np.exp(-self.sigmoid * np.asarray(x)))

    def skip_empty_class(self) -> bool:
        return True

    def to_string(self) -> str:
        return "binary sigmoid:%g" % self.sigmoid


class MulticlassSoftmax(ObjectiveFunction):
    """multiclass_objective.hpp:16-137; score shaped (num_class, num_data)."""
    name = "multiclass"

    def __init__(self, config: Optional[Config] = None, num_class: int = None):
        self.num_class = int(config.num_class if config is not None else num_class)

    def init(self, metadata: Metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label).astype(np.int32)
        if lab.min() < 0 or lab.max() >= self.num_class:
            Log.fatal("Label must be in [0, %d)", self.num_class)
        self.label_int = jnp.asarray(lab)

    def get_gradients(self, score):
        score = score.reshape(self.num_class, self.num_data)
        p = jnp.exp(score - jnp.max(score, axis=0, keepdims=True))
        p = p / jnp.sum(p, axis=0, keepdims=True)
        onehot = (jnp.arange(self.num_class)[:, None] == self.label_int[None, :])
        g = p - onehot.astype(p.dtype)
        h = 2.0 * p * (1.0 - p)
        if self.weights is not None:
            g = g * self.weights[None, :]
            h = h * self.weights[None, :]
        return g.reshape(-1), h.reshape(-1)

    def convert_output(self, x):
        x = np.asarray(x, dtype=np.float64)
        e = np.exp(x - x.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)

    def skip_empty_class(self) -> bool:
        return True

    def num_tree_per_iteration(self) -> int:
        return self.num_class

    def num_predict_one_row(self) -> int:
        return self.num_class

    def to_string(self) -> str:
        return "multiclass num_class:%d" % self.num_class


class MulticlassOVA(ObjectiveFunction):
    """multiclass_objective.hpp:139-248: per-class BinaryLogloss."""
    name = "multiclassova"

    def __init__(self, config: Optional[Config] = None, num_class: int = None,
                 sigmoid: float = 1.0):
        if config is not None:
            self.num_class = int(config.num_class)
            self.sigmoid = float(config.sigmoid)
            self.binary = [BinaryLogloss(config, is_pos=_make_is_pos(i))
                           for i in range(self.num_class)]
        else:
            self.num_class = int(num_class)
            self.sigmoid = float(sigmoid)
            self.binary = [BinaryLogloss(sigmoid=sigmoid, is_pos=_make_is_pos(i))
                           for i in range(self.num_class)]

    def init(self, metadata: Metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        for b in self.binary:
            b.init(metadata, num_data)

    def get_gradients(self, score):
        score = score.reshape(self.num_class, self.num_data)
        gs, hs = [], []
        for i, b in enumerate(self.binary):
            g, h = b.get_gradients(score[i])
            gs.append(g)
            hs.append(h)
        return jnp.concatenate(gs), jnp.concatenate(hs)

    def convert_output(self, x):
        return 1.0 / (1.0 + np.exp(-self.sigmoid * np.asarray(x)))

    def skip_empty_class(self) -> bool:
        return True

    def num_tree_per_iteration(self) -> int:
        return self.num_class

    def num_predict_one_row(self) -> int:
        return self.num_class

    def to_string(self) -> str:
        return "multiclassova num_class:%d sigmoid:%g" % (self.num_class, self.sigmoid)


def _make_is_pos(i: int):
    return lambda label: np.asarray(label).astype(np.int32) == i


def default_label_gain(size: int = 31) -> List[float]:
    """label_gain = 2^i - 1 (src/io/config.cpp:273-277)."""
    return [float((1 << i) - 1) for i in range(size)]


def get_discounts(n: int) -> np.ndarray:
    """DCG position discount 1/log2(2+i) (dcg_calculator.cpp:22-25)."""
    return 1.0 / np.log2(2.0 + np.arange(n))


class LambdarankNDCG(ObjectiveFunction):
    """rank_objective.hpp:19-244: pairwise lambdas weighted by |ΔNDCG|.

    Exact sigmoid instead of the reference's 1M-entry lookup table (same
    function, no quantization error); per-query numpy vectorization of the
    O(n^2) pair loop.
    """
    name = "lambdarank"

    def __init__(self, config: Optional[Config] = None):
        config = config or Config()
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0.0:
            Log.fatal("Sigmoid param %f should be greater than zero", self.sigmoid)
        self.label_gain = np.asarray(config.label_gain or default_label_gain())
        self.optimize_pos_at = int(config.max_position)

    def init(self, metadata: Metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("Lambdarank tasks require query information")
        self.qb = np.asarray(metadata.query_boundaries)
        self.labels_np = np.asarray(metadata.label)
        self.weights_np = None if metadata.weights is None else np.asarray(metadata.weights)
        self.num_queries = len(self.qb) - 1
        self.inverse_max_dcgs = np.zeros(self.num_queries)
        for q in range(self.num_queries):
            lab = self.labels_np[self.qb[q]:self.qb[q + 1]]
            m = _max_dcg_at_k(self.optimize_pos_at, lab, self.label_gain)
            self.inverse_max_dcgs[q] = 1.0 / m if m > 0.0 else m
        self._build_device_layout()

    def _build_device_layout(self) -> None:
        """Padded per-query layout for the jitted gradient program.

        Queries are BUCKETED by padded width (powers of two): each bucket
        is a (Qb, w) table, so total table memory is O(sum of padded query
        sizes) <= 2N — one 5000-doc query among 500k small ones costs its
        own tiny bucket instead of widening every row to 5000.  Within a
        bucket the design is the `vmap over padded query segments` of
        SURVEY.md §7 step 4 replacing rank_objective.hpp:19-244's per-query
        OMP loop; a handful of bucket-shaped jit calls per iteration
        replaces the reference's single loop.
        """
        counts = np.diff(self.qb)
        nq = self.num_queries
        self._dev_label_gain = jnp.asarray(self.label_gain.astype(np.float32))
        self._dev_sigmoid = float(self.sigmoid)
        widths = np.maximum(
            2, 2 ** np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64))
        self._buckets = []
        for w in np.unique(widths):
            qs = np.flatnonzero(widths == w)
            c = counts[qs]
            w = int(w)
            slot = np.arange(w)[None, :]
            valid = slot < c[:, None]
            idx = self.qb[:-1][qs][:, None] + slot       # (Qb, w)
            idx = np.minimum(idx, self.num_data - 1)     # clamp padding
            labels = np.where(valid,
                              self.labels_np[idx].astype(np.int32), 0)
            # this bucket's score-vector rows, and their table slots, in
            # matching (row-major) order — the device program returns the
            # per-row values and the caller scatters them into (N,)
            qi, si = np.nonzero(valid)
            rows = idx[valid]
            tabpos = qi * w + si
            # block the query axis so the pairwise (w, w) tensors stay
            # bounded: ~64MB of f32 pair matrices per block
            blk = max(1, min(len(qs), int(16_000_000 // (w * w)) or 1))
            self._buckets.append({
                "idx": jnp.asarray(idx.astype(np.int32)),
                "valid": jnp.asarray(valid),
                "labels": jnp.asarray(labels),
                "counts": jnp.asarray(c.astype(np.int32)),
                "inv": jnp.asarray(
                    self.inverse_max_dcgs[qs].astype(np.float32)),
                "discounts": jnp.asarray(
                    get_discounts(w).astype(np.float32)),
                "rows": jnp.asarray(rows.astype(np.int32)),
                "tabpos": jnp.asarray(tabpos.astype(np.int32)),
                "block": blk,
            })

    def get_gradients(self, score):
        """Jitted padded-query lambdas — no host round-trip per iteration.

        The numpy implementation (get_gradients_host) is kept as the oracle
        for tests/test_objectives parity checks.
        """
        score = jnp.asarray(score, jnp.float32)
        lam = jnp.zeros(self.num_data, jnp.float32)
        hes = jnp.zeros(self.num_data, jnp.float32)
        for b in self._buckets:
            lb, hb = _lambdarank_device(
                score, b["idx"], b["valid"], b["labels"], b["counts"],
                b["inv"], b["discounts"], self._dev_label_gain,
                b["tabpos"], self._dev_sigmoid, b["block"])
            lam = lam.at[b["rows"]].set(lb)
            hes = hes.at[b["rows"]].set(hb)
        return _apply_weights(lam, hes, self.weights)

    def get_gradients_host(self, score):
        """Reference-shaped numpy path (rank_objective.hpp:100-190)."""
        score = np.asarray(score, dtype=np.float64)
        lambdas = np.zeros(self.num_data, dtype=np.float32)
        hessians = np.zeros(self.num_data, dtype=np.float32)
        for q in range(self.num_queries):
            s, e = self.qb[q], self.qb[q + 1]
            self._one_query(score[s:e], self.labels_np[s:e],
                            self.inverse_max_dcgs[q],
                            lambdas[s:e], hessians[s:e])
        if self.weights_np is not None:
            lambdas *= self.weights_np
            hessians *= self.weights_np
        return jnp.asarray(lambdas), jnp.asarray(hessians)

    def _one_query(self, score, label, inv_max_dcg, out_l, out_h):
        cnt = len(score)
        if cnt <= 1 or inv_max_dcg <= 0:
            return
        sorted_idx = np.argsort(-score, kind="stable")
        ranked_score = score[sorted_idx]
        ranked_label = label[sorted_idx].astype(np.int32)
        best_score = ranked_score[0]
        worst_idx = cnt - 1
        if worst_idx > 0 and ranked_score[worst_idx] == -np.inf:
            worst_idx -= 1
        worst_score = ranked_score[worst_idx]
        discounts = get_discounts(cnt)
        gains = self.label_gain[ranked_label]
        # pair (i=high rank pos, j=low rank pos) matrices over ranked order
        valid = (ranked_label[:, None] > ranked_label[None, :])
        valid &= np.isfinite(ranked_score)[:, None] & np.isfinite(ranked_score)[None, :]
        delta_score = ranked_score[:, None] - ranked_score[None, :]
        dcg_gap = gains[:, None] - gains[None, :]
        paired_discount = np.abs(discounts[:, None] - discounts[None, :])
        delta_ndcg = dcg_gap * paired_discount * inv_max_dcg
        if best_score != worst_score:
            delta_ndcg = delta_ndcg / (0.01 + np.abs(delta_score))
        p_lambda = 2.0 / (1.0 + np.exp(2.0 * delta_score * self.sigmoid))
        p_hess = p_lambda * (2.0 - p_lambda)
        p_lambda = np.where(valid, -p_lambda * delta_ndcg, 0.0)
        p_hess = np.where(valid, 2.0 * p_hess * delta_ndcg, 0.0)
        lam = p_lambda.sum(axis=1) - p_lambda.sum(axis=0)
        hes = p_hess.sum(axis=1) + p_hess.sum(axis=0)
        out_l[sorted_idx] += lam.astype(np.float32)
        out_h[sorted_idx] += hes.astype(np.float32)


def _lambdarank_one_query(s, labels, cnt, inv_max_dcg, discounts,
                          label_gain, sigmoid):
    """Pairwise lambdas for ONE padded query (rank_objective.hpp:100-190).

    s: (qmax,) scores with padding at -inf; labels: (qmax,) int32;
    cnt: scalar real count.  Returns (lam, hes) in ORIGINAL segment order.
    """
    sorted_idx = jnp.argsort(-s)                   # stable: ties keep order
    rs = s[sorted_idx]
    rl = labels[sorted_idx]
    gains = label_gain[rl]
    finite = jnp.isfinite(rs)
    valid = (rl[:, None] > rl[None, :]) & finite[:, None] & finite[None, :]
    delta_score = rs[:, None] - rs[None, :]
    dcg_gap = gains[:, None] - gains[None, :]
    paired_discount = jnp.abs(discounts[:, None] - discounts[None, :])
    delta_ndcg = dcg_gap * paired_discount * inv_max_dcg
    best_score = rs[0]
    wi = jnp.maximum(cnt - 1, 0)
    wi = jnp.where((wi > 0) & jnp.isneginf(rs[wi]), wi - 1, wi)
    worst_score = rs[wi]
    norm = jnp.where(best_score != worst_score,
                     1.0 / (0.01 + jnp.abs(delta_score)), 1.0)
    delta_ndcg = delta_ndcg * norm
    p_lambda = 2.0 / (1.0 + jnp.exp(2.0 * delta_score * sigmoid))
    p_hess = p_lambda * (2.0 - p_lambda)
    p_lambda = jnp.where(valid, -p_lambda * delta_ndcg, 0.0)
    p_hess = jnp.where(valid, 2.0 * p_hess * delta_ndcg, 0.0)
    lam = jnp.sum(p_lambda, axis=1) - jnp.sum(p_lambda, axis=0)
    hes = jnp.sum(p_hess, axis=1) + jnp.sum(p_hess, axis=0)
    live = (cnt > 1) & (inv_max_dcg > 0.0)
    lam = jnp.where(live, lam, 0.0)
    hes = jnp.where(live, hes, 0.0)
    inv = jnp.argsort(sorted_idx)                  # unsort to segment order
    return lam[inv], hes[inv]


@functools.partial(jax.jit, static_argnums=(9, 10))
def _lambdarank_device(score, idx, valid, labels, counts, inv_max_dcg,
                       discounts, label_gain, tab_pos, sigmoid,
                       block):
    """Per-bucket lambdas: (R,) values for the rows whose table slots are
    tab_pos (callers scatter them back into the (N,) gradient vectors)."""
    from jax import lax
    nq, qmax = idx.shape
    s = jnp.where(valid, score[idx].astype(jnp.float32), -jnp.inf)
    pad_q = (-nq) % block
    if pad_q:
        zpadi = lambda a: jnp.concatenate(
            [a, jnp.zeros((pad_q,) + a.shape[1:], a.dtype)])
        s = jnp.concatenate([s, jnp.full((pad_q, qmax), -jnp.inf, s.dtype)])
        labels = zpadi(labels)
        counts = zpadi(counts)
        inv_max_dcg = zpadi(inv_max_dcg)
    nb = (nq + pad_q) // block

    per_query = jax.vmap(_lambdarank_one_query,
                         in_axes=(0, 0, 0, 0, None, None, None))

    def one_block(args):
        sb, lb, cb, ib = args
        return per_query(sb, lb, cb, ib, discounts, label_gain, sigmoid)

    lam, hes = lax.map(one_block,
                       (s.reshape(nb, block, qmax),
                        labels.reshape(nb, block, qmax),
                        counts.reshape(nb, block),
                        inv_max_dcg.reshape(nb, block)))
    lam = lam.reshape(-1)[tab_pos]                 # (R,) gather-back
    hes = hes.reshape(-1)[tab_pos]
    return lam, hes


def _max_dcg_at_k(k: int, label: np.ndarray, label_gain: np.ndarray) -> float:
    """DCGCalculator::CalMaxDCGAtK (dcg_calculator.cpp:28-50)."""
    k = min(k, len(label))
    sorted_label = np.sort(label.astype(np.int32))[::-1][:k]
    return float((label_gain[sorted_label] * get_discounts(k)).sum())


_OBJECTIVE_FACTORY = {
    "regression": RegressionL2loss,
    "regression_l2": RegressionL2loss,
    "mean_squared_error": RegressionL2loss,
    "mse": RegressionL2loss,
    "regression_l1": RegressionL1loss,
    "mean_absolute_error": RegressionL1loss,
    "mae": RegressionL1loss,
    "huber": RegressionHuberLoss,
    "fair": RegressionFairLoss,
    "poisson": RegressionPoissonLoss,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "softmax": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "multiclass_ova": MulticlassOVA,
    "ova": MulticlassOVA,
    "ovr": MulticlassOVA,
    "lambdarank": LambdarankNDCG,
}


def create_objective(name: str, config: Config) -> Optional[ObjectiveFunction]:
    """ObjectiveFunction::CreateObjectiveFunction (objective_function.cpp:9-35)."""
    if name in ("none", "null", "custom", "na"):
        return None
    cls = _OBJECTIVE_FACTORY.get(name)
    if cls is None:
        Log.fatal("Unknown objective type name: %s", name)
    if cls in (RegressionL2loss,):
        return cls()
    return cls(config)


def load_objective_from_string(s: str) -> Optional[ObjectiveFunction]:
    """Round-trip from model files (objective_function.cpp:37-56)."""
    toks = s.split()
    if not toks:
        return None
    name = toks[0]
    kv = {}
    for t in toks[1:]:
        if ":" in t:
            k, _, v = t.partition(":")
            kv[k] = v
    if name == "binary":
        return BinaryLogloss(sigmoid=float(kv.get("sigmoid", 1.0)))
    if name == "multiclass":
        return MulticlassSoftmax(num_class=int(kv.get("num_class", 2)))
    if name == "multiclassova":
        return MulticlassOVA(num_class=int(kv.get("num_class", 2)),
                             sigmoid=float(kv.get("sigmoid", 1.0)))
    cfg = Config()
    cls = _OBJECTIVE_FACTORY.get(name)
    if cls is None:
        return None
    if cls is RegressionL2loss:
        return cls()
    return cls(cfg)
