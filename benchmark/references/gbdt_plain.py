"""Plain reference for binary-logloss gradient boosting on binned columns.

It imports nothing of the program and takes nothing the program made but
its answers: the trees of the first boosting steps and the score vector
after each.  It holds each answer against what it computes itself from
the bins and labels that the benchmark's generator wrote:

* gradients and hessians of the logloss, in float64, from its own score
  chain (score 0 at the start: the binary objective does not boost from
  the average);
* every row's leaf, by walking the tree over the bins (a row goes left
  when its bin is at most the threshold; a row in the column's default
  bin is placed as if its bin were the split's `default_bin_for_zero`);
* every leaf's row count and its value ``-G / (H + lambda_l2)`` times the
  learning rate, and from those the next score of every row;
* for a sample of each tree's splits, drawn from the seed and with the
  root in it, the node's full histogram over every column and bin
  (float32 at `highest`, on the accelerator, a shard at a time), the
  best gain any (column, threshold, default placement) reaches there,
  and the gain of the split the program made.

What comes out is a dict of gaps (see `follow`); the caller holds each
against its limit.
"""
import numpy as np

MIN_SUM_HESSIAN = 1e-3        # LightGBM's default min_sum_hessian_in_leaf


def gradients(score, label):
    """Logloss gradient and hessian per row, label in {0, 1}."""
    p = 1.0 / (1.0 + np.exp(-score))
    return p - label, p * (1.0 - p)


def logloss(score, label):
    # log(1 + exp(-z)) with z = score for positives, -score for negatives
    z = np.where(label > 0, score, -score)
    return float(np.mean(np.logaddexp(0.0, -z)))


def leaf_of_rows(tree, shards, default_bin):
    """Leaf index of every row; `shards` are (columns, rows) bin arrays."""
    out = []
    for xt in shards:
        rows = xt.shape[1]
        leaf = np.full(rows, -1, np.int32)
        # rows sitting at each internal node, from the root down
        stack = [(0, np.arange(rows))]
        while stack:
            node, idx = stack.pop()
            b = np.asarray(xt[int(tree["split_feature"][node])][idx],
                           np.int64)
            b = np.where(b == default_bin, int(tree["dbz"][node]), b)
            left = b <= int(tree["threshold_bin"][node])
            for child, sub in ((int(tree["left_child"][node]), idx[left]),
                               (int(tree["right_child"][node]), idx[~left])):
                if child < 0:
                    leaf[sub] = ~child
                elif sub.size:
                    stack.append((child, sub))
        out.append(leaf)
    return np.concatenate(out)


def leaves_under(tree):
    """(internal nodes, leaves) bool: which leaves hang under each split."""
    ni = int(tree["num_leaves"]) - 1
    under = np.zeros((ni, ni + 1), bool)
    children = [(int(tree["left_child"][n]), int(tree["right_child"][n]))
                for n in range(ni)]
    order, stack = [], [0]
    while stack:                    # parents before their children
        node = stack.pop()
        order.append(node)
        stack.extend(c for c in children[node] if c >= 0)
    for node in reversed(order):
        for child in children[node]:
            if child < 0:
                under[node, ~child] = True
            else:
                under[node] |= under[child]
    return under


def sample_nodes(tree, how_many, rng):
    """The root and a seeded draw of the other splits."""
    ni = int(tree["num_leaves"]) - 1
    others = rng.permutation(np.arange(1, ni))[:max(how_many - 1, 0)]
    return np.concatenate([[0], np.sort(others)]).astype(np.int64)


def node_histograms(shards, per_step, num_bin, column_block=8):
    """Histograms of sampled nodes over every column and bin.

    per_step: a list of ``(leaf, g, h, member)`` with leaf (rows,) the
    row's leaf, g/h (rows,) float64 and member (leaves, nodes) bool.
    Returns one (columns, num_bin, nodes, 2) float64 array a step.  One
    pass over the shards serves all steps.  Plain ``jax.numpy``: a one-hot
    of the bins against the masked gradients, float32 at `highest`.
    """
    import jax
    import jax.numpy as jnp

    columns = shards[0].shape[0]
    pad = (-columns) % column_block
    widths = [int(m.shape[1]) for _, _, _, m in per_step]

    def shard_hist(xt, leaf, g, h, members):
        ws = []
        for k, member in enumerate(members):
            inside = member[leaf[k]].astype(jnp.float32)       # (rows, S)
            ws += [inside * g[k][:, None], inside * h[k][:, None]]
        w = jnp.concatenate(ws, axis=1)                         # (rows, K)
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
        blocks = xt.reshape(-1, column_block, xt.shape[1])
        bins = jnp.arange(num_bin, dtype=xt.dtype)

        def block(xb):
            onehot = (xb[:, None, :] == bins[None, :, None])
            return jnp.einsum("cbr,rk->cbk", onehot.astype(jnp.float32), w,
                              precision="highest")

        out = jax.lax.map(block, blocks)
        return out.reshape(-1, num_bin, w.shape[1])[:columns]

    fn = jax.jit(shard_hist)
    members = [jnp.asarray(m) for _, _, _, m in per_step]
    total = None
    start = 0
    for xt in shards:
        rows = xt.shape[1]
        sl = slice(start, start + rows)
        part = fn(jnp.asarray(np.ascontiguousarray(xt)),
                  jnp.asarray(np.stack([s[0][sl] for s in per_step])),
                  jnp.asarray(np.stack([s[1][sl] for s in per_step]),
                              jnp.float32),
                  jnp.asarray(np.stack([s[2][sl] for s in per_step]),
                              jnp.float32),
                  members)
        total = part if total is None else total + part
        start += rows
    total = np.asarray(total, np.float64)
    out, at = [], 0
    for s in widths:
        g = total[:, :, at:at + s]
        h = total[:, :, at + s:at + 2 * s]
        out.append(np.stack([g, h], axis=-1))
        at += 2 * s
    return out


def _placed(hist, default_bin, dbz):
    """The histogram with the default bin's mass moved to bin `dbz`."""
    if dbz == default_bin:
        return hist
    moved = hist.copy()
    moved[..., dbz, :] += hist[..., default_bin, :]
    moved[..., default_bin, :] -= hist[..., default_bin, :]
    return moved


HESSIAN_MARGIN = 0.01   # sides this close to the minimum could go either way


def split_gains(hist, default_bin, lambda_l2=0.0,
                min_sum_hessian=MIN_SUM_HESSIAN):
    """Gain of every candidate split of one node.

    hist: (columns, bins, 2).  Returns two (3, columns, bins - 1) arrays:
    the gain with the default bin placed first, in its natural place and
    last, for each threshold, -inf where a side's hessian is under the
    minimum.  The first array asks each side for the minimum and a margin
    more, the second for a margin less: a program that sums in another
    order may rule a side on the line in or out, so the best is taken
    from the first and a split the program made is looked up in the
    second.  (``min_data_in_leaf`` is not looked at: at 1 it asks no more
    than a side that holds a row, and such a side has a hessian.)
    """
    bins = hist.shape[1]
    total = hist[0].sum(axis=0)
    parent = total[0] ** 2 / (total[1] + lambda_l2)
    strict, loose = [], []
    for dbz in (0, default_bin, bins - 1):
        left = np.cumsum(_placed(hist, default_bin, dbz), axis=1)[:, :-1]
        lg, lh = left[..., 0], left[..., 1]
        rg, rh = total[0] - lg, total[1] - lh
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = (lg ** 2 / (lh + lambda_l2) + rg ** 2 / (rh + lambda_l2)
                    - parent)
        side = np.minimum(lh, rh)
        strict.append(np.where(
            side >= min_sum_hessian * (1 + HESSIAN_MARGIN), gain, -np.inf))
        loose.append(np.where(
            side >= min_sum_hessian * (1 - HESSIAN_MARGIN), gain, -np.inf))
    return np.stack(strict), np.stack(loose)


def follow(trees, scores, shards, label, params, default_bin, num_bin,
           check_nodes, seed):
    """Follow the program's first steps and measure how far it strays.

    trees: one dict a step (``num_leaves, split_feature, threshold_bin,
    dbz, left_child, right_child, leaf_value, leaf_count``); scores: the
    program's score vector after each step.  Returns the gaps:

    count_mismatch        rows, summed over leaves and steps, by which a
                          leaf's count differs from the rows that walk to it
    leaf_value_gap        worst leaf of any step: |value - reference's| over
                          the larger of |reference's| and the step's median
    leaf_value_gap_step0  the same over the first step alone, where every
                          gradient (+-0.5) and hessian (0.25) is exact in
                          bf16 and float8, so no precision is in it: what is
                          left is the program's logic
    score_gap             worst row: |score - reference's| over the root mean
                          square of the reference's change in that step
    score_gap_step0       the same after the first step alone
    loss_gap              worst step: |loss - reference's| over reference's
    split_gain_gap_step0  worst sampled split of the first step: how far the
                          gain of the split made lies below the best the
                          reference finds in that node, over that best
    per_step              the four gaps step by step, for the log
    """
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_hess = float(params.get("min_sum_hessian_in_leaf", MIN_SUM_HESSIAN))
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
            # no tree, or no score: nothing to follow, every gap is open
            return {name: float("inf") for name in gaps}
        g, h = gradients(ref_score, label)
        leaf = leaf_of_rows(tree, shards, default_bin)
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
        ref_loss = logloss(ref_score, label)
        loss_gap = abs(
            logloss(np.asarray(score, np.float64), label) - ref_loss) / ref_loss
        gaps["loss_gap"] = max(gaps["loss_gap"], loss_gap)
        steps.append({"leaves": nl, "leaf_value_gap": value_gap,
                      "score_gap": score_gap, "loss_gap": loss_gap,
                      "split_gain_gap": 0.0})
        nodes = sample_nodes(tree, check_nodes, rng)
        picked.append(nodes)
        per_step.append((leaf, g, h, leaves_under(tree)[nodes].T))
    hists = node_histograms(shards, per_step, num_bin)
    for tree, nodes, hist, seen in zip(trees, picked, hists, steps):
        for j, node in enumerate(nodes):
            gains, made_gains = split_gains(hist[:, :, j, :], default_bin,
                                            l2, min_hess)
            best = float(gains.max())
            dbz = int(tree["dbz"][node])
            place = {0: 0, default_bin: 1, num_bin - 1: 2}.get(dbz)
            t = int(tree["threshold_bin"][node])
            if place is None or not 0 <= t < num_bin - 1:
                seen["split_gain_gap"] = float("inf")
                continue
            made = float(made_gains[place,
                                    int(tree["split_feature"][node]), t])
            if not best > 0.0:
                # nothing clears the margin here: only ask that the split
                # made is one the reference allows
                best = made if np.isfinite(made) else float("nan")
            gap = (best - made) / best if best > 0.0 else float("inf")
            seen["split_gain_gap"] = max(seen["split_gain_gap"], gap)
    gaps["split_gain_gap_step0"] = steps[0]["split_gain_gap"]
    gaps["per_step"] = steps        # for the log; no limit is held to it
    return gaps
