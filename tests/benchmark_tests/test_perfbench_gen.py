"""The generator: deterministic in the seed, and its directory opens with
``Dataset.from_binned`` and trains."""
import numpy as np

from benchmark import gen


def _make(config, seed, path):
    gen.generate(config, seed, str(path))
    shards, label = gen.open_shards(str(path))
    return np.concatenate([np.asarray(s) for s in shards], axis=1), label


def test_same_seed_same_bytes_other_seed_other_data(tiny_config, tmp_path):
    big = 2 ** 31 + 12345           # more than 32 signed bits hold
    a, la = _make(tiny_config, big, tmp_path / "a")
    b, lb = _make(tiny_config, big, tmp_path / "b")
    c, lc = _make(tiny_config, big + 1, tmp_path / "c")
    assert np.array_equal(a, b) and np.array_equal(la, lb)
    assert a.shape == c.shape == (tiny_config["columns"], tiny_config["rows"])
    assert (a != c).mean() > 0.9 and (la != lc).mean() > 0.2


def test_bins_are_uniform_and_labels_learnable(tiny_config, tmp_path):
    x, label = _make(tiny_config, 7, tmp_path / "d")
    num_bin = tiny_config["params"]["max_bin"]
    assert x.dtype == np.uint8 and x.max() == num_bin - 1 and x.min() == 0
    counts = np.bincount(x.ravel(), minlength=num_bin)
    assert counts.min() > 0.8 * counts.mean()
    assert 0.4 < label.mean() < 0.6
    # the label follows a linear score of the centred bins
    centred = x.astype(np.float64) - (num_bin - 1) / 2.0
    corr = [abs(np.corrcoef(col, label)[0, 1]) for col in centred]
    assert max(corr) > 0.1


def test_shard_rows_cover_the_table():
    assert gen.shard_rows_of(10, 4) == [4, 4, 2]
    assert gen.shard_rows_of(8, 4) == [4, 4]
    assert sum(gen.shard_rows_of(1_200_000, 1 << 18)) == 1_200_000


def test_mappers_cut_a_normal_at_its_quantiles():
    (m,) = gen.mapper_dicts(1, 63)
    assert m["num_bin"] == 63 and len(m["bin_upper_bound"]) == 63
    assert m["bin_upper_bound"][-1] == float("inf")
    assert m["bin_upper_bound"][m["default_bin"]] >= 0.0
    assert m["bin_upper_bound"][m["default_bin"] - 1] < 0.0


def test_directory_opens_with_from_binned_and_trains(tiny_config, tmp_path):
    import lightgbm_tpu as lgb

    gen.generate(tiny_config, 11, str(tmp_path / "e"))
    ds = lgb.Dataset.from_binned(str(tmp_path / "e"),
                                 params=dict(tiny_config["params"]))
    bst = lgb.Booster(dict(tiny_config["params"]), ds)
    for _ in range(2):
        bst.update()
    assert ds._handle.num_data == tiny_config["rows"]
    assert bst.num_trees() == 2
    bst._gbdt._materialize()
    assert bst._gbdt.models[0].num_leaves == 31
