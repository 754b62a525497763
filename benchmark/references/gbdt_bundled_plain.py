"""Plain reference for binary-logloss gradient boosting on a BUNDLED store.

The table's byte columns are Exclusive-Feature-Bundling groups: several
model features share one column (LightGBM's FeatureGroup layout, which
docs/OutOfCore.md's header states as ``bundle_groups`` and the features'
mappers).  This file reads that header and the shards and decodes a
feature's bin from its group's byte by its own few lines; it imports
nothing of the program and takes nothing the program made but its
answers.  What does not depend on the store (gradients, loss, the tree's
shape, the sampled nodes, the one-hot histogram of byte columns) is
``gbdt_plain``'s.

The layout, as the format documents it.  A group of one feature holds
that feature's bins as they are.  In a group of several, byte 0 means
"every feature at its default bin"; the features follow one another from
byte 1 on, feature ``f`` taking ``num_bin[f]`` bytes, one fewer where its
default bin is 0 (that bin is never stored).  So with ``off`` the
feature's first byte and ``adj`` 1 where its default bin is 0:

    bin = byte - off + adj   if off <= byte < off + span
          default_bin        otherwise (another feature wrote the byte,
                             or none did)

It follows the trees over all the model's features, and for the sampled
nodes builds every FEATURE's histogram from the groups' (a feature's
stored bins are a slice of its group's histogram, its default bin is the
node's total less that slice), so a wrong bundle view or a wrong
default-bin subtraction in the program shows as a split that the
reference's own best beats.
"""
import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "benchmark_references_gbdt_plain", os.path.join(_HERE, "gbdt_plain.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)

GROUP_BINS = 256            # a byte column's histogram


def layout_from_header(header):
    """The features' places in the byte columns, from a directory's
    header: arrays over the features (`group`, `off`, `adj`, `span`,
    `num_bin`, `default_bin`)."""
    mappers = header["bin_mappers"]
    used = header["used_feature_idx"]
    num_bin = np.array([mappers[r]["num_bin"] for r in used], np.int64)
    default = np.array([mappers[r]["default_bin"] for r in used], np.int64)
    groups = header.get("bundle_groups")
    if groups is None:
        groups = [[f] for f in range(len(used))]
    n = len(used)
    group, off = np.zeros(n, np.int64), np.zeros(n, np.int64)
    adj, span = np.zeros(n, np.int64), num_bin.copy()
    for g, feats in enumerate(groups):
        group[feats] = g
        if len(feats) == 1:
            continue
        at = 1
        for f in feats:
            adj[f] = 1 if default[f] == 0 else 0
            span[f] = num_bin[f] - adj[f]
            off[f] = at
            at += span[f]
    return {"group": group, "off": off, "adj": adj, "span": span,
            "num_bin": num_bin, "default_bin": default}


def feature_bin(byte, f, layout):
    """Feature `f`'s bins from its group's bytes."""
    byte = np.asarray(byte, np.int64)
    off, span = int(layout["off"][f]), int(layout["span"][f])
    inside = (byte >= off) & (byte < off + span)
    return np.where(inside, byte - off + int(layout["adj"][f]),
                    int(layout["default_bin"][f]))


def leaf_of_rows(tree, shards, layout):
    """Leaf index of every row; `shards` are (groups, rows) byte arrays."""
    out = []
    for xt in shards:
        rows = xt.shape[1]
        leaf = np.full(rows, -1, np.int32)
        stack = [(0, np.arange(rows))]
        while stack:
            node, idx = stack.pop()
            f = int(tree["split_feature"][node])
            b = feature_bin(xt[int(layout["group"][f])][idx], f, layout)
            b = np.where(b == int(layout["default_bin"][f]),
                         int(tree["dbz"][node]), b)
            left = b <= int(tree["threshold_bin"][node])
            for child, sub in ((int(tree["left_child"][node]), idx[left]),
                               (int(tree["right_child"][node]), idx[~left])):
                if child < 0:
                    leaf[sub] = ~child
                elif sub.size:
                    stack.append((child, sub))
        out.append(leaf)
    return np.concatenate(out)


def feature_histograms(ghist, layout):
    """(features, bins, 2) of one node from its (groups, 256, 2) byte
    histograms: the stored bins are a slice of the group's, the default
    bin is the node's total less them."""
    num_bin, default = layout["num_bin"], layout["default_bin"]
    n, width = len(num_bin), int(num_bin.max())
    total = ghist[0].sum(axis=0)
    bins = np.arange(width)[None, :]
    byte = layout["off"][:, None] + bins - layout["adj"][:, None]
    stored = (bins < num_bin[:, None]) & (bins != default[:, None])
    hist = np.where(
        stored[..., None],
        ghist[layout["group"][:, None], np.clip(byte, 0, GROUP_BINS - 1)],
        0.0)
    hist[np.arange(n), default] = total[None, :] - hist.sum(axis=1)
    return hist


def split_gains(hist, layout, lambda_l2, min_sum_hessian):
    """Gain of every candidate split of one node, as
    ``gbdt_plain.split_gains`` gives it, with each feature's own bin
    count and default bin: two (3, features, bins - 1) arrays (the
    default bin placed first, in its natural place, last), -inf where a
    side's hessian is under the minimum or the feature has no such
    threshold."""
    num_bin, default = layout["num_bin"], layout["default_bin"]
    n, width = hist.shape[0], hist.shape[1]
    total = hist[0].sum(axis=0)
    with np.errstate(divide="ignore", invalid="ignore"):
        parent = total[0] ** 2 / (total[1] + lambda_l2)
    rows = np.arange(n)
    at_default = hist[rows, default]
    real = np.arange(width - 1)[None, :] < (num_bin - 1)[:, None]
    strict, loose = [], []
    for dbz in (np.zeros(n, np.int64), default, num_bin - 1):
        moved = hist.copy()
        moved[rows, default] -= at_default
        moved[rows, dbz] += at_default
        left = np.cumsum(moved, axis=1)[:, :-1]
        lg, lh = left[..., 0], left[..., 1]
        rg, rh = total[0] - lg, total[1] - lh
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = (lg ** 2 / (lh + lambda_l2) + rg ** 2 / (rh + lambda_l2)
                    - parent)
        side = np.minimum(lh, rh)
        margin = plain.HESSIAN_MARGIN
        strict.append(np.where(
            real & (side >= min_sum_hessian * (1 + margin)), gain, -np.inf))
        loose.append(np.where(
            real & (side >= min_sum_hessian * (1 - margin)), gain, -np.inf))
    return np.stack(strict), np.stack(loose)


def follow(trees, scores, shards, label, params, header, check_nodes, seed):
    """Follow the program's first steps on the bundled store; the gaps of
    ``gbdt_plain.follow``, under the same names.

    shards: (groups, rows) byte arrays as the generator wrote them;
    header: the directory's ``header.json`` as a dict.  A tree's
    ``split_feature`` counts the model's features, not the byte columns.
    """
    layout = layout_from_header(header)
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_hess = float(params.get("min_sum_hessian_in_leaf",
                                plain.MIN_SUM_HESSIAN))
    rng = np.random.default_rng(int(seed))
    label = np.asarray(label, np.float64)
    ref_score = np.zeros(label.shape[0])
    gaps = {"count_mismatch": 0.0, "leaf_value_gap": 0.0,
            "leaf_value_gap_step0": 0.0, "score_gap": 0.0,
            "score_gap_step0": 0.0, "loss_gap": 0.0,
            "split_gain_gap_step0": 0.0}
    per_step, picked, steps = [], [], []
    for tree, score in zip(trees, scores):
        nl = int(tree["num_leaves"])
        if nl < 2 or not np.all(np.isfinite(score)):
            return {name: float("inf") for name in gaps}
        g, h = plain.gradients(ref_score, label)
        leaf = leaf_of_rows(tree, shards, layout)
        count = np.bincount(leaf, minlength=nl)
        gaps["count_mismatch"] += float(
            np.abs(count - np.asarray(tree["leaf_count"][:nl])).sum())
        sum_g = np.bincount(leaf, weights=g, minlength=nl)
        sum_h = np.bincount(leaf, weights=h, minlength=nl)
        with np.errstate(divide="ignore", invalid="ignore"):
            value = np.where(count > 0, -sum_g / (sum_h + l2) * lr, 0.0)
        floor = np.median(np.abs(value))
        value_gap = float(np.max(
            np.abs(np.asarray(tree["leaf_value"][:nl]) - value)
            / np.maximum(np.abs(value), floor)))
        gaps["leaf_value_gap"] = max(gaps["leaf_value_gap"], value_gap)
        step = value[leaf]
        ref_score = ref_score + step
        rms = float(np.sqrt(np.mean(step * step)))
        score_gap = float(
            np.max(np.abs(np.asarray(score, np.float64) - ref_score)) / rms)
        gaps["score_gap"] = max(gaps["score_gap"], score_gap)
        if not steps:
            gaps["leaf_value_gap_step0"] = value_gap
            gaps["score_gap_step0"] = score_gap
        ref_loss = plain.logloss(ref_score, label)
        loss_gap = abs(plain.logloss(np.asarray(score, np.float64), label)
                       - ref_loss) / ref_loss
        gaps["loss_gap"] = max(gaps["loss_gap"], loss_gap)
        steps.append({"leaves": nl, "leaf_value_gap": value_gap,
                      "score_gap": score_gap, "loss_gap": loss_gap,
                      "split_gain_gap": 0.0})
        nodes = plain.sample_nodes(tree, check_nodes, rng)
        picked.append(nodes)
        per_step.append((leaf, g, h, plain.leaves_under(tree)[nodes].T))
    # the byte columns' histograms, 256 bins each, two columns a block
    ghists = plain.node_histograms(shards, per_step, GROUP_BINS,
                                   column_block=2)
    for tree, nodes, ghist, seen in zip(trees, picked, ghists, steps):
        for j, node in enumerate(nodes):
            hist = feature_histograms(ghist[:, :, j, :], layout)
            gains, made_gains = split_gains(hist, layout, l2, min_hess)
            best = float(gains.max())
            f = int(tree["split_feature"][node])
            dbz = int(tree["dbz"][node])
            place = {0: 0, int(layout["default_bin"][f]): 1,
                     int(layout["num_bin"][f]) - 1: 2}.get(dbz)
            t = int(tree["threshold_bin"][node])
            if place is None or not 0 <= t < int(layout["num_bin"][f]) - 1:
                seen["split_gain_gap"] = float("inf")
                continue
            made = float(made_gains[place, f, t])
            if not best > 0.0:
                best = made if np.isfinite(made) else float("nan")
            gap = (best - made) / best if best > 0.0 else float("inf")
            seen["split_gain_gap"] = max(seen["split_gain_gap"], gap)
    gaps["split_gain_gap_step0"] = steps[0]["split_gain_gap"]
    gaps["per_step"] = steps
    return gaps
