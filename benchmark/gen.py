"""The one data generator: a pre-binned directory straight from the seed.

A configuration's file says how many rows, columns and bins; this writes
the directory ``lgb.Dataset.from_binned`` opens (the layout is
docs/OutOfCore.md's: ``header.json``, raw column-major uint8 shards with
their CRCs, ``label.npy``).  Bins and labels are drawn on the device in
one jitted call a shard, so a run's set-up holds no float matrix and no
binning pass.  Each column's bins are uniform over its mapper's
``num_bin``, which is what quantile bins of a continuous column look
like; the label is the sign of a sparse linear score of the centred bins
plus noise.  The same seed gives the same bytes on the same platform;
another seed gives other data, of the same sizes.
"""
import json
import os
import statistics
import time
import zlib

import numpy as np


def make_key(seed):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def mapper_dicts(columns, num_bin):
    """``BinMapper.to_dict`` entries of `columns` standard-normal numeric
    columns cut at their `num_bin` quantiles (written by hand: no sample is
    binned)."""
    norm = statistics.NormalDist()
    bounds = [norm.inv_cdf(k / num_bin) for k in range(1, num_bin)]
    bounds.append(float("inf"))
    default_bin = next(i for i, b in enumerate(bounds) if 0.0 <= b)
    one = {"num_bin": num_bin, "is_trivial": False, "sparse_rate": 0.0,
           "bin_type": 0, "min_val": -5.0, "max_val": 5.0,
           "default_bin": default_bin, "bin_upper_bound": bounds}
    return [one] * columns


def _weights(key, columns, weighted_share):
    """The label's linear score: about `weighted_share` of the columns
    carry a normal weight, scaled so that the score's spread is 2."""
    import jax
    import jax.numpy as jnp

    k_mask, k_w = jax.random.split(key)
    mask = jax.random.uniform(k_mask, (columns,)) < weighted_share
    w = jnp.where(mask, jax.random.normal(k_w, (columns,)), 0.0)
    return w / jnp.sqrt(jnp.sum(w * w) + 1e-12) * 2.0


def _shard_fn(columns, rows, num_bin):
    import jax
    import jax.numpy as jnp

    def shard(key, w):
        k_bins, k_noise = jax.random.split(key)
        bits = jax.random.bits(k_bins, (columns, rows), jnp.uint16)
        bins = ((bits.astype(jnp.uint32) * num_bin) >> 16).astype(jnp.uint8)
        # centred to mean 0 and spread 1, as a uniform over num_bin bins
        spread = ((num_bin * num_bin - 1) / 12.0) ** 0.5
        centred = (bins.astype(jnp.float32) - (num_bin - 1) / 2.0) / spread
        score = jnp.dot(w, centred, precision="highest")
        label = score + jax.random.normal(k_noise, (rows,)) > 0.0
        return bins, label.astype(jnp.float32)

    return jax.jit(shard)


def shard_rows_of(total_rows, shard_rows):
    """Row counts of the shards: all `shard_rows` but the last."""
    out = []
    left = int(total_rows)
    while left > 0:
        out.append(min(left, int(shard_rows)))
        left -= out[-1]
    return out


def generate(config, seed, out_dir):
    """Write the binned directory of `config` for `seed` under `out_dir`.

    Returns ``{"rows", "columns", "num_bin", "bytes", "shards", "seconds"}``
    (seconds spent fetching from the device, writing and checksumming).  The
    directory's header goes through the program's own
    ``BinnedWriter.finalize``; the shard files are the documented raw
    column-major bytes, which is the device array's own layout here.
    """
    import jax
    from lightgbm_tpu.io.binned_format import BinnedWriter, shard_name
    from lightgbm_tpu.io.binning import BinMapper

    rows, columns = int(config["rows"]), int(config["columns"])
    num_bin = int(config["params"]["max_bin"])
    sizes = shard_rows_of(rows, config.get("shard_rows", 1 << 18))
    key = make_key(seed)
    w = _weights(jax.random.fold_in(key, 0), columns,
                 float(config.get("weighted_share", 0.7)))
    fns = {}

    def dispatch(i):
        n = sizes[i]
        if n not in fns:
            fns[n] = _shard_fn(columns, n, num_bin)
        return fns[n](jax.random.fold_in(key, i + 1), w)

    writer = BinnedWriter(out_dir, columns, np.uint8)
    labels = []
    seconds = {"fetch": 0.0, "write": 0.0, "crc": 0.0}
    pending = dispatch(0)
    for i in range(len(sizes)):
        bins, label = pending
        pending = dispatch(i + 1) if i + 1 < len(sizes) else None
        t0 = time.time()
        host = np.asarray(bins)             # (columns, rows), C order
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

    mappers = [BinMapper.from_dict(d)
               for d in mapper_dicts(columns, num_bin)]
    writer.finalize(
        num_total_features=columns, used_feature_idx=list(range(columns)),
        feature_names=["Column_%d" % i for i in range(columns)],
        max_bin=num_bin, bin_mappers=mappers, bundle_groups=None,
        metadata=_Meta)
    return {"rows": rows, "columns": columns, "num_bin": num_bin,
            "bytes": rows * columns, "shards": len(sizes),
            "seconds": {k: round(v, 3) for k, v in seconds.items()}}


def open_shards(path):
    """The directory's shards as ``(columns, rows)`` uint8 memmaps, and its
    labels: read by the benchmark's own code, not through the program."""
    with open(os.path.join(path, "header.json")) as f:
        header = json.load(f)
    columns = int(header["num_columns"])
    shards = [np.memmap(os.path.join(path, s["file"]), dtype=np.uint8,
                        mode="r", shape=(columns, int(s["rows"])))
              for s in header["shards"]]
    label = np.load(os.path.join(path, header["label"]))
    return shards, label
