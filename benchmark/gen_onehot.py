"""The one-hot generator: a pre-binned, BUNDLED directory from the seed.

A configuration's ``source_columns`` are a few categorical columns (each
one-hot coded: one two-bin column a level, default bin 0) and a few
numerical ones (``bins`` quantile bins, as ``gen.mapper_dicts`` cuts
them).  The model sees ``columns`` features; the store holds what
Exclusive Feature Bundling makes of them, and the bundles are the
program's own: a sample of ``bin_construct_sample_cnt`` rows of
per-feature bins goes through ``lightgbm_tpu.io.bundle
.find_feature_groups`` exactly as ``io/dataset.py`` sends its binning
sample, and the groups it returns go into the header through
``BinnedWriter.finalize``.  The table's source columns are then drawn on
the device, a shard at a time, and written as uint8 GROUP bins in the
layout ``io/bundle.py build_layout`` documents: bin 0 of a bundle is
"every feature at its default", feature ``f`` starts at ``bin_off[f]``,
a later feature overwrites an earlier one on a conflict row, a group of
one feature keeps its raw bins.  The directory is docs/OutOfCore.md's.

Levels follow a Zipf law (level ``l`` with weight ``(l + 1) ** -s``);
row ``i`` of the table, for ``i`` under a column's level count, holds
level ``i``, so that no level is absent at any size.  The label is the
sign of a sparse score over the features (a weight a one-hot level, a
weight times the centred bin of a numerical column) plus noise.
Everything but the levels' frequencies follows the run's seed, as in
``gen.py``: the score's weights, the binning sample (so the groups' bin
counts, which the generator's and the booster's programs hold as
constants), the rows and the noise.
"""
import os
import time
import zlib

import numpy as np

from benchmark import gen

SAMPLE_ROWS = 200000        # upstream's bin_construct_sample_cnt


def source_columns(config):
    """``[(levels or bins, is_categorical)]`` a source column; they have
    to make the configuration's `columns` features."""
    cols = [(int(c.get("levels") or c["bins"]), "levels" in c)
            for c in config["source_columns"]]
    features = sum(card if cat else 1 for card, cat in cols)
    if features != int(config["columns"]):
        raise ValueError("source_columns make %d features, the "
                         "configuration says %d columns"
                         % (features, int(config["columns"])))
    return cols


def feature_table(cols):
    """``[(source column, level or -1)]`` a feature, in feature order."""
    return [(c, level if cat else -1) for c, (card, cat) in enumerate(cols)
            for level in range(card if cat else 1)]


def level_probabilities(card, exponent):
    p = (np.arange(card, dtype=np.float64) + 1.0) ** -float(exponent)
    return p / p.sum()


def mapper_dicts(cols, exponent):
    """``BinMapper.to_dict`` entries of the features, written by hand: a
    one-hot column is what ``find_bin`` makes of a 0/1 column (two bins,
    zero in bin 0), a numerical one is ``gen.mapper_dicts``'s."""
    out = []
    for card, cat in cols:
        if not cat:
            out += gen.mapper_dicts(1, card)
            continue
        for p in level_probabilities(card, exponent):
            out.append({"num_bin": 2, "is_trivial": False,
                        "sparse_rate": float(1.0 - p), "bin_type": 0,
                        "min_val": 0.0, "max_val": 1.0, "default_bin": 0,
                        "bin_upper_bound": [1e-20, float("inf")]})
    return out


def feature_bins(values, cols):
    """(rows, features) uint8 per-feature bins of (source columns, rows)
    source values: the table before it is bundled."""
    rows = values.shape[1]
    out = []
    for c, (card, cat) in enumerate(cols):
        if cat:
            block = np.zeros((rows, card), np.uint8)
            block[np.arange(rows), values[c]] = 1
        else:
            block = values[c].astype(np.uint8)[:, None]
        out.append(block)
    return np.concatenate(out, axis=1)


def score_tables(key, cols, exponent, weighted_share):
    """One float32 table a source column: what a row's value there adds
    to the label's score.  About `weighted_share` of the features carry a
    normal weight; the score is centred and scaled to a spread of 2."""
    import jax

    features = len(feature_table(cols))
    k_mask, k_w = jax.random.split(key)
    mask = np.asarray(jax.random.uniform(k_mask, (features,))) \
        < weighted_share
    w = np.where(mask, np.asarray(jax.random.normal(k_w, (features,)),
                                  np.float64), 0.0)
    tables, variance, at = [], 0.0, 0
    for card, cat in cols:
        if cat:
            p = level_probabilities(card, exponent)
            t = w[at:at + card] - float(np.dot(p, w[at:at + card]))
            variance += float(np.dot(p, t * t))
            at += card
        else:
            spread = ((card * card - 1) / 12.0) ** 0.5
            t = w[at] * (np.arange(card) - (card - 1) / 2.0) / spread
            variance += float(w[at] ** 2)
            at += 1
        tables.append(t)
    scale = 2.0 / (variance + 1e-12) ** 0.5
    return [np.asarray(t * scale, np.float32) for t in tables]


def _values_fn(cols, rows, exponent):
    """(key, first row) -> (source columns, rows) int32 source values."""
    import jax
    import jax.numpy as jnp

    cdfs = [jnp.asarray(np.cumsum(level_probabilities(card, exponent)),
                        jnp.float32) if cat else None for card, cat in cols]

    def values(key, first_row):
        at = first_row + jnp.arange(rows, dtype=jnp.int32)
        out = []
        for c, (card, cat) in enumerate(cols):
            k = jax.random.fold_in(key, c)
            if cat:
                drawn = jnp.searchsorted(cdfs[c], jax.random.uniform(
                    k, (rows,)), side="right").astype(jnp.int32)
                drawn = jnp.minimum(drawn, card - 1)
                out.append(jnp.where(at < card, at, drawn))
            else:
                bits = jax.random.bits(k, (rows,), jnp.uint16)
                out.append(((bits.astype(jnp.uint32) * card) >> 16)
                           .astype(jnp.int32))
        return jnp.stack(out)

    return values


def group_tables(groups, features, cols, num_bin, default_bin):
    """How a group's byte follows from the source values, from the
    layout ``io/bundle.py build_layout`` documents.  A group of one
    feature is ``("raw", source column, level)``; a bundle is
    ``("bundle", [(source column, table)])`` in feature order, where
    ``table[value]`` is the group bin that column's feature writes at
    that value, 0 where it writes none."""
    out = []
    for feats in groups:
        if len(feats) == 1:
            out.append(("raw",) + features[feats[0]])
            continue
        tables, off = {}, 1             # bin 0: every feature at default
        for f in feats:
            c, level = features[f]
            adj = 1 if default_bin[f] == 0 else 0
            t = tables.setdefault(c, np.zeros(cols[c][0], np.int32))
            if level >= 0:              # one-hot: bin 1 at its level
                t[level] = off + 1 - adj
            else:
                bins = np.arange(cols[c][0])
                t[:] = np.where(bins != default_bin[f], bins + off - adj, 0)
            off += num_bin[f] - adj
        out.append(("bundle", sorted(tables.items())))
    return out


def _shard_fn(cols, rows, exponent, tables, score_tabs):
    import jax
    import jax.numpy as jnp

    values_of = _values_fn(cols, rows, exponent)
    score_tabs = [jnp.asarray(t) for t in score_tabs]

    def shard(key, first_row):
        k_values, k_noise = jax.random.split(key)
        values = values_of(k_values, first_row)
        columns = []
        for entry in tables:
            if entry[0] == "raw":
                _, c, level = entry
                columns.append(values[c] if level < 0
                               else (values[c] == level).astype(jnp.int32))
                continue
            col = jnp.zeros((rows,), jnp.int32)
            for c, table in entry[1]:   # feature order: later overwrites
                wrote = jnp.asarray(table)[values[c]]
                col = jnp.where(wrote > 0, wrote, col)
            columns.append(col)
        score = sum(t[values[c]] for c, t in enumerate(score_tabs))
        label = score + jax.random.normal(k_noise, (rows,)) > 0.0
        return jnp.stack(columns).astype(jnp.uint8), label.astype(jnp.float32)

    return jax.jit(shard)


def find_groups(config, key, cols, num_bin, default_bin):
    """The program's own EFB on a sample of the per-feature bins, as
    ``io/dataset.py`` calls it: the groups (feature lists) and their bin
    counts."""
    import jax
    from lightgbm_tpu.io.bundle import find_feature_groups

    rows = int(config["rows"])
    sample = min(SAMPLE_ROWS, rows)
    exponent = float(config["zipf_exponent"])
    values = np.asarray(jax.jit(_values_fn(cols, sample, exponent))(key, 0))
    params = config["params"]
    layout = find_feature_groups(
        feature_bins(values, cols), num_bin, default_bin,
        float(params.get("max_conflict_rate", 0.0)),
        int(params.get("min_data_in_leaf", 20)), rows)
    if layout is None:
        return [[f] for f in range(len(num_bin))], [int(b) for b in num_bin]
    return ([list(map(int, g)) for g in layout.groups],
            [int(b) for b in layout.num_group_bins])


def generate(config, seed, out_dir, log=print):
    """Write the bundled binned directory of `config` for `seed`.

    Returns ``{"rows", "columns", "groups", "group_bins", "bytes",
    "shards", "seconds"}``: `columns` are the model's features, `groups`
    the store's byte columns."""
    import jax
    from lightgbm_tpu.io.binned_format import BinnedWriter, shard_name
    from lightgbm_tpu.io.binning import BinMapper

    rows = int(config["rows"])
    exponent = float(config["zipf_exponent"])
    cols = source_columns(config)
    features = feature_table(cols)
    dicts = mapper_dicts(cols, exponent)
    num_bin = np.asarray([d["num_bin"] for d in dicts], np.int32)
    default_bin = np.asarray([d["default_bin"] for d in dicts], np.int32)
    key = gen.make_key(seed)
    t0 = time.time()
    groups, group_bins = find_groups(config, jax.random.fold_in(key, 0),
                                     cols, num_bin, default_bin)
    seconds = {"efb": time.time() - t0, "fetch": 0.0, "write": 0.0,
               "crc": 0.0}
    log("EFB bundled %d features into %d groups; features a group %s; "
        "bins a group %s" % (len(features), len(groups),
                             [len(g) for g in groups], group_bins))
    tables = group_tables(groups, features, cols, num_bin, default_bin)
    score_tabs = score_tables(jax.random.fold_in(key, 1), cols, exponent,
                              float(config.get("weighted_share", 0.7)))
    sizes = gen.shard_rows_of(rows, config.get("shard_rows", 1 << 18))
    starts = np.concatenate([[0], np.cumsum(sizes)])
    fns = {}

    def dispatch(i):
        n = sizes[i]
        if n not in fns:
            fns[n] = _shard_fn(cols, n, exponent, tables, score_tabs)
        return fns[n](jax.random.fold_in(key, i + 2), np.int32(starts[i]))

    writer = BinnedWriter(out_dir, len(groups), np.uint8)
    labels = []
    pending = dispatch(0)
    for i in range(len(sizes)):
        bins, label = pending
        pending = dispatch(i + 1) if i + 1 < len(sizes) else None
        t0 = time.time()
        host = np.asarray(bins)             # (groups, rows), C order
        del bins
        t1 = time.time()
        with open(os.path.join(out_dir, shard_name(i)), "wb") as f:
            host.tofile(f)
        t2 = time.time()
        writer.append_written(sizes[i], zlib.crc32(host) & 0xFFFFFFFF)
        seconds["fetch"] += t1 - t0
        seconds["write"] += t2 - t1
        seconds["crc"] += time.time() - t2
        labels.append(np.asarray(label))
        del host

    class _Meta:
        label = np.concatenate(labels)
        weights = query_boundaries = init_score = None

    writer.finalize(
        num_total_features=len(features),
        used_feature_idx=list(range(len(features))),
        feature_names=["Column_%d" % i for i in range(len(features))],
        max_bin=int(config["params"]["max_bin"]),
        bin_mappers=[BinMapper.from_dict(d) for d in dicts],
        bundle_groups=groups, metadata=_Meta)
    return {"rows": rows, "columns": len(features), "groups": len(groups),
            "group_bins": group_bins, "bytes": rows * len(groups),
            "shards": len(sizes),
            "seconds": {k: round(v, 3) for k, v in seconds.items()}}


def source_values(config, seed):
    """(source columns, rows) int32: the whole table's source values as
    `generate` draws them, shard by shard (for tests, at small sizes:
    ``feature_bins`` of it is the table before it is bundled)."""
    import jax

    cols = source_columns(config)
    exponent = float(config["zipf_exponent"])
    key = gen.make_key(seed)
    sizes = gen.shard_rows_of(int(config["rows"]),
                              config.get("shard_rows", 1 << 18))
    out, first = [], 0
    for i, n in enumerate(sizes):
        k_values, _ = jax.random.split(jax.random.fold_in(key, i + 2))
        out.append(np.asarray(_values_fn(cols, n, exponent)(
            k_values, np.int32(first))))
        first += n
    return np.concatenate(out, axis=1)
