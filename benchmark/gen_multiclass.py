"""The multiclass generator: `gen_onehot`'s bundled directory with a
class label.

A configuration's ``source_columns`` (numerical columns at ``bins``
quantile bins, categorical ones one-hot coded) become the model's
features and the program's own EFB groups exactly as ``gen_onehot``
makes them: this file runs ``gen_onehot.generate`` itself, on a private
instance of that module whose two label functions are this file's.  The
label is one of the configuration's ``classes``, drawn from a softmax
over a sparse linear score a class (a weight a one-hot level and a class,
a weight times the centred bin of a numerical column) plus a bias a
class.  The biases are fitted on a sample of rows so that the expected
class shares are the configuration's ``class_shares``; the weights, the
sample, the rows and the draw all follow the run's seed, as in
``gen.py``.  The source values are drawn as ``gen_onehot`` draws them
(the same key a shard, split the same way), so
``gen_onehot.source_values`` gives this table's values too.
"""
import importlib.util

import numpy as np

from benchmark import gen, gen_onehot

FIT_ROWS = 200000           # rows the class biases are fitted on
FIT_STEPS = 200


def class_shares(config):
    """The configuration's class shares, normalised, one a class."""
    shares = np.asarray(config["class_shares"], np.float64)
    if shares.shape != (int(config["classes"]),):
        raise ValueError("class_shares has %d entries, classes is %d"
                         % (shares.size, int(config["classes"])))
    return shares / shares.sum()


def _logits(values, tables, bias):
    """(classes, rows) float64 logits of (source columns, rows) values."""
    out = np.repeat(bias[:, None], values.shape[1], axis=1)
    for c, table in enumerate(tables):
        out += table[:, values[c]]
    return out


def fit_bias(values, tables, shares, steps=FIT_STEPS):
    """Biases a class such that the softmax's mean over `values`' rows
    is `shares`: a fixed point of b += log(share) - log(mean p)."""
    bias = np.log(shares)
    base = _logits(values, tables, np.zeros_like(bias))
    for _ in range(steps):
        z = base + bias[:, None]
        p = np.exp(z - z.max(axis=0))
        p /= p.sum(axis=0)
        bias += np.log(shares) - np.log(p.mean(axis=1))
    return bias


def score_tables(config):
    """`gen_onehot.score_tables` for k classes: one (k, values) float32
    table a source column and a (k,) bias, the bias fitted on
    ``FIT_ROWS`` rows drawn from the key."""
    classes = int(config["classes"])
    shares = class_shares(config)

    def tables_of(key, cols, exponent, weighted_share):
        import jax

        per_class = [gen_onehot.score_tables(jax.random.fold_in(key, c),
                                             cols, exponent, weighted_share)
                     for c in range(classes)]
        tables = [np.stack([t[col] for t in per_class]).astype(np.float64)
                  for col in range(len(cols))]
        values = np.asarray(jax.jit(gen_onehot._values_fn(
            cols, FIT_ROWS, exponent))(jax.random.fold_in(key, classes), 0))
        bias = fit_bias(values, tables, shares)
        return ([np.asarray(t, np.float32) for t in tables],
                np.asarray(bias, np.float32))

    return tables_of


def _shard_fn(cols, rows, exponent, tables, score_tabs):
    """`gen_onehot._shard_fn` with the label drawn from the classes'
    softmax: the same group bytes, the same key split."""
    import jax
    import jax.numpy as jnp

    values_of = gen_onehot._values_fn(cols, rows, exponent)
    bytes_of = gen_onehot._shard_fn(cols, rows, exponent, tables,
                                    [np.zeros(card, np.float32)
                                     for card, _ in cols])
    class_tabs = [jnp.asarray(t) for t in score_tabs[0]]
    bias = jnp.asarray(score_tabs[1])

    def shard(key, first_row):
        k_values, k_label = jax.random.split(key)
        values = values_of(k_values, first_row)
        logits = bias[:, None] + sum(t[:, values[c]]
                                     for c, t in enumerate(class_tabs))
        label = jax.random.categorical(k_label, logits, axis=0)
        return bytes_of(key, first_row)[0], label.astype(jnp.float32)

    return jax.jit(shard)


def _onehot_instance(config):
    """A private instance of ``gen_onehot`` whose label functions are
    this file's (the shared module stays as it is)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_gen_onehot_multiclass", gen_onehot.__file__)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.score_tables = score_tables(config)
    module._shard_fn = _shard_fn
    return module


def generate(config, seed, out_dir, log=print):
    """Write the bundled binned directory of `config` for `seed`, labels
    in ``0 .. classes - 1``.  Returns `gen_onehot.generate`'s dict and
    ``shares``, the classes' shares of the rows written."""
    info = _onehot_instance(config).generate(config, seed, out_dir, log)
    _, label = gen.open_shards(out_dir)
    counts = np.bincount(label.astype(np.int64),
                         minlength=int(config["classes"]))
    info["shares"] = [round(float(c) / label.size, 5) for c in counts]
    log("class shares %s (configuration: %s)"
        % (info["shares"], [round(float(s), 5)
                            for s in class_shares(config)]))
    return info
