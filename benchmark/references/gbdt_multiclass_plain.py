"""Plain reference for multiclass-softmax gradient boosting on a BUNDLED
store.

k trees a step, one a class, all from the gradients of one softmax:

    p = softmax(score) over the k classes of a row
    g = p - 1[label = c],  h = 2 p (1 - p)

as ``lightgbm_tpu/objectives.py`` and the reference's
``multiclass_objective.hpp`` compute them, in float64 from its own score
chain (every score 0 at the start: softmax does not boost from the
average).  The gradients of a step are taken once, before any of its k
trees is applied; class c's tree takes g[c], h[c], its leaves' values
are ``-G / (H + lambda_l2)`` times the learning rate, and its step is
added to row c of the score.

The store's byte columns are EFB groups: a feature's bin is decoded from
its group's byte by ``gbdt_bundled_plain`` (imported, not copied), which
also builds each feature's histogram from its group's and the gain of
every candidate split.  Every row's leaf is found by walking the tree
over the decoded bins in plain ``jax.numpy`` on the accelerator (all
rows through one split at a time, parents first: the numpy walk of the
binary references would take minutes for 21 trees at this size); the
counts, the leaves' sums and the losses are numpy float64.  It imports nothing
of the program and takes nothing the program made but its answers.
"""
import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "benchmark_references_gbdt_bundled_plain",
    os.path.join(_HERE, "gbdt_bundled_plain.py"))
bundled = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bundled)
plain = bundled.plain

FETCH_ROWS = 1 << 22        # rows a device-to-host copy of the leaf ids
BLOCK_ROWS = 1 << 16        # rows a block of the float64 passes


def _blocks(rows):
    """Row slices of ``BLOCK_ROWS``: the float64 passes run a block at a
    time, so their temporaries stay small."""
    return [slice(i, min(i + BLOCK_ROWS, rows))
            for i in range(0, rows, BLOCK_ROWS)]


def gradients(score, label):
    """(k, rows) gradient and hessian of the softmax loss, label in
    0 .. k - 1: p = softmax(score), g = p - 1[label = c], h = 2p(1 - p)."""
    g, h = np.empty_like(score), np.empty_like(score)
    for rows in _blocks(score.shape[1]):
        z = score[:, rows]
        p = np.exp(z - z.max(axis=0))
        p /= p.sum(axis=0)
        h[:, rows] = 2.0 * p * (1.0 - p)
        p[label[rows], np.arange(p.shape[1])] -= 1.0
        g[:, rows] = p
    return g, h


def multi_logloss(score, label):
    """Mean over rows of -log p[label], in float64."""
    total = 0.0
    for rows in _blocks(score.shape[1]):
        z = np.asarray(score[:, rows], np.float64)
        top = z.max(axis=0)
        lse = top + np.log(np.exp(z - top).sum(axis=0))
        total += float(np.sum(lse - z[label[rows], np.arange(z.shape[1])]))
    return total / score.shape[1]


def device_store(shards):
    """The (groups, rows) bytes on the accelerator, shard by shard."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.asarray(np.ascontiguousarray(s))
                            for s in shards], axis=1)


def walker(layout):
    """A jitted walk: (store, tree arrays) -> every row's leaf, from the
    bins `layout` decodes (``gbdt_bundled_plain.feature_bin``'s rule).
    The rows move down one split at a time, parents before children: a
    split's feature, threshold and children are scalars, so no row
    gathers from the tree's tables (a gather a row and a level is what
    the accelerator does slowly)."""
    import jax
    import jax.numpy as jnp

    at = {k: jnp.asarray(layout[k], jnp.int32)
          for k in ("group", "off", "adj", "span", "default_bin")}

    def walk(xt, feature, threshold, dbz, left, right):
        def split(n, node):
            f = feature[n]
            byte = jax.lax.dynamic_index_in_dim(
                xt, at["group"][f], keepdims=False).astype(jnp.int32)
            off, default = at["off"][f], at["default_bin"][f]
            inside = (byte >= off) & (byte < off + at["span"][f])
            b = jnp.where(inside, byte - off + at["adj"][f], default)
            b = jnp.where(b == default, dbz[n], b)
            child = jnp.where(b <= threshold[n], left[n], right[n])
            return jnp.where(node == n, child, node)

        node = jax.lax.fori_loop(0, feature.shape[0], split,
                                 jnp.zeros(xt.shape[1], jnp.int32))
        return ~node

    return jax.jit(walk)


def parents_first(tree):
    """The tree's splits renumbered so that a parent comes before its
    children: (old index a new place, the tree's arrays in that order,
    children renumbered too)."""
    ni = int(tree["num_leaves"]) - 1
    left = np.asarray(tree["left_child"][:ni], np.int64)
    right = np.asarray(tree["right_child"][:ni], np.int64)
    order, stack = [], [0]
    while stack:
        n = stack.pop()
        order.append(n)
        stack.extend(c for c in (left[n], right[n]) if c >= 0)
    place = np.empty(ni, np.int64)
    place[order] = np.arange(ni)

    def renumber(child):
        return np.where(child >= 0, place[np.maximum(child, 0)], child)

    return [np.asarray(tree["split_feature"][:ni])[order],
            np.asarray(tree["threshold_bin"][:ni])[order],
            np.asarray(tree["dbz"][:ni])[order],
            renumber(left[order]), renumber(right[order])]


def leaf_of_rows(tree, xt, walk):
    """Every row's leaf, on the host, fetched by pieces."""
    import jax.numpy as jnp

    leaf = walk(xt, *(jnp.asarray(np.asarray(a, np.int32))
                      for a in parents_first(tree)))
    rows = leaf.shape[0]
    return np.concatenate([np.asarray(leaf[i:i + FETCH_ROWS])
                           for i in range(0, rows, FETCH_ROWS)])


def split_gain_gap(tree, nodes, ghist, layout, lambda_l2, min_sum_hessian):
    """Worst sampled split of one tree: how far the gain of the split made
    lies under the best the reference finds in that node, over that best
    (``gbdt_bundled_plain``'s histograms and gains); and the root split's
    gain as the tree records it and as the reference finds it (``nodes``
    starts at the root)."""
    worst = 0.0
    root = (float("nan"), float("nan"))
    for j, node in enumerate(nodes):
        hist = bundled.feature_histograms(ghist[:, :, j, :], layout)
        gains, made_gains = bundled.split_gains(hist, layout, lambda_l2,
                                                min_sum_hessian)
        best = float(gains.max())
        f = int(tree["split_feature"][node])
        place = {0: 0, int(layout["default_bin"][f]): 1,
                 int(layout["num_bin"][f]) - 1: 2}.get(int(tree["dbz"][node]))
        t = int(tree["threshold_bin"][node])
        if place is None or not 0 <= t < int(layout["num_bin"][f]) - 1:
            return float("inf"), root
        made = float(made_gains[place, f, t])
        if node == 0:
            root = (float(tree["split_gain"][0]), made)
        if not best > 0.0:
            best = made if np.isfinite(made) else float("nan")
        worst = max(worst, (best - made) / best if best > 0.0
                    else float("inf"))
    return worst, root


def follow(trees, scores, shards, label, params, header, check_nodes, seed):
    """Follow the program's first steps; the gaps of
    ``gbdt_plain.follow``, under the same names, over every class tree.

    trees: one list a step of the k class trees (dicts as
    ``gbdt_plain.follow`` takes them, with each split's recorded
    ``split_gain``), class order; scores: the
    program's (k, rows) score after each step; shards: (groups, rows)
    bytes; header: the directory's ``header.json`` as a dict.

    count_mismatch        rows by which a leaf's count differs from the
                          rows that walk to it, every leaf of every tree
    leaf_value_gap_step0  worst leaf of the first step's k trees, over the
                          larger of the reference's value and the tree's
                          median
    score_gap_step0       worst row and class after the first step, over
                          the root mean square of that class's step
    split_gain_gap_step0  the first step's k root splits: the gains the
                          trees record, summed, against the reference's
                          gains of those splits, summed, over the latter.
                          A gain is a sum over the rows (a leaf's value is a
                          ratio of two), so trees grown on part of the rows
                          record part of the gain; summed over the classes,
                          a class whose root gains little does not weigh
    loss_gap              worst step: |multi_logloss - reference's| over
                          the reference's

    and, read for the log with no limit, ``split_choice_gap_step0``: the
    worst sampled split of the first step's k trees, how far its gain lies
    under the best the reference finds in its node (what
    ``split_gain_gap_step0`` is in the binary references).  Step 0 is not
    exact here (g is 1/7 or -6/7, h 12/49, none a bf16 number), so near
    ties between candidates make it read up to 1 on sound runs.
    """
    layout = bundled.layout_from_header(header)
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_hess = float(params.get("min_sum_hessian_in_leaf",
                                plain.MIN_SUM_HESSIAN))
    rng = np.random.default_rng(int(seed))
    label = np.asarray(label).astype(np.int64)
    k = len(trees[0])
    ref_score = np.zeros((k, label.size))
    gaps = {"count_mismatch": 0.0, "leaf_value_gap_step0": 0.0,
            "score_gap_step0": 0.0, "loss_gap": 0.0,
            "split_gain_gap_step0": 0.0, "split_choice_gap_step0": 0.0}
    if any(int(t["num_leaves"]) < 2 for step in trees for t in step) or \
            not all(np.all(np.isfinite(s)) for s in scores):
        return {name: float("inf") for name in gaps}
    xt = device_store(shards)
    walk = walker(layout)
    per_step, first = [], []
    for s, (step_trees, score) in enumerate(zip(trees, scores)):
        g, h = gradients(ref_score, label)
        seen = {"leaves": [], "leaf_value_gap": 0.0, "score_gap": 0.0}
        for c, tree in enumerate(step_trees):
            nl = int(tree["num_leaves"])
            leaf = leaf_of_rows(tree, xt, walk)
            count = np.bincount(leaf, minlength=nl)
            gaps["count_mismatch"] += float(
                np.abs(count - np.asarray(tree["leaf_count"][:nl])).sum())
            sum_g = np.bincount(leaf, weights=g[c], minlength=nl)
            sum_h = np.bincount(leaf, weights=h[c], minlength=nl)
            with np.errstate(divide="ignore", invalid="ignore"):
                value = np.where(count > 0, -sum_g / (sum_h + l2) * lr, 0.0)
            floor = np.median(np.abs(value))
            value_gap = float(np.max(
                np.abs(np.asarray(tree["leaf_value"][:nl]) - value)
                / np.maximum(np.abs(value), floor)))
            change = value[leaf]
            ref_score[c] += change
            rms = float(np.sqrt(np.mean(change * change)))
            score_gap = float(np.max(np.abs(
                np.asarray(score[c], np.float64) - ref_score[c])) / rms)
            seen["leaves"].append(nl)
            seen["leaf_value_gap"] = max(seen["leaf_value_gap"], value_gap)
            seen["score_gap"] = max(seen["score_gap"], score_gap)
            if s == 0:
                nodes = plain.sample_nodes(tree, check_nodes, rng)
                first.append((leaf, g[c], h[c],
                              plain.leaves_under(tree)[nodes].T, nodes))
        ref_loss = multi_logloss(ref_score, label)
        seen["loss_gap"] = abs(multi_logloss(score, label) - ref_loss) \
            / ref_loss
        gaps["loss_gap"] = max(gaps["loss_gap"], seen["loss_gap"])
        per_step.append(seen)
    del xt
    gaps["leaf_value_gap_step0"] = per_step[0]["leaf_value_gap"]
    gaps["score_gap_step0"] = per_step[0]["score_gap"]
    # the byte columns' histograms of the first step's sampled nodes, 256
    # bins each, two columns a block: one pass over the shards
    ghists = plain.node_histograms(shards, [f[:4] for f in first],
                                   bundled.GROUP_BINS, column_block=2)
    split_gaps = [split_gain_gap(tree, f[4], ghist, layout, l2, min_hess)
                  for tree, f, ghist in zip(trees[0], first, ghists)]
    gaps["split_choice_gap_step0"] = max(worst for worst, _ in split_gaps)
    recorded, made = (sum(root[i] for _, root in split_gaps)
                      for i in (0, 1))
    gaps["split_gain_gap_step0"] = (abs(recorded - made) / made
                                    if made > 0.0 else float("inf"))
    gaps["per_step"] = per_step
    return gaps
