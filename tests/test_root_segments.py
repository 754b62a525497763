"""The one-hot pass contracts no one-hot wider than SEGMENT_BINS
(ops/histogram.py `_segments`): a wider column goes in as 64-bin segments
of the 64-bin class and comes back laid side by side, while a store whose
every column is at most 64 bins wide traces the program it always did."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import timers
from lightgbm_tpu.ops import histogram
from lightgbm_tpu.ops.histogram import (SEGMENT_BINS, leaf_histogram_onehot,
                                        leaf_histogram_scatter,
                                        onehot_pass_shape)
from lightgbm_tpu.ops.wave import col_bin_pads

EXPO_BINS = (13, 32, 8, 23, 256, 59, 256, 59, 63, 63)
EXPO_PADS = (32, 32, 32, 32, 256, 64, 256, 64, 64, 64)
EDGES = (0, 63, 64, 127, 128, 191, 192, 255)

# store -> (bins a column, num_bins, col_pads)
STORES = {
    "expo": (EXPO_BINS, 256, EXPO_PADS),
    "uniform-256": ((256,) * 6, 256, ()),
    "uniform-255": ((255,) * 5, 255, ()),
    "ragged-96-200": ((90, 200, 20), 200, col_bin_pads((90, 200, 20), 200)),
    "edges": ((256,) * 4, 256, ()),
}


def _store(name, n=1700, seed=39):
    bins, num_bins, pads = STORES[name]
    rng = np.random.default_rng(seed)
    if name == "edges":
        X = rng.choice(EDGES, size=(n, len(bins)))
    else:
        X = np.stack([rng.integers(0, b, size=n) for b in bins], axis=1)
    w3 = rng.normal(size=(n, 3)).astype(np.float32)
    leaf_id = rng.integers(0, 2, size=n).astype(np.int32)
    args = (jnp.asarray(X.astype(np.uint8)), jnp.asarray(w3[:, 0]),
            jnp.abs(jnp.asarray(w3[:, 1])), jnp.asarray(leaf_id), 0,
            jnp.ones(n, jnp.float32))
    return args, num_bins, pads


@pytest.mark.parametrize("chunk", [16384, 512], ids=["one-chunk", "chunks"])
@pytest.mark.parametrize("hilo", [False, True], ids=["bf16", "hilo"])
@pytest.mark.parametrize("store", list(STORES))
def test_the_segmented_pass_matches_scatter(store, hilo, chunk):
    """Every bin lands in its own column's bin, the segment edges too, and
    the bins past a column's width stay empty."""
    args, num_bins, pads = _store(store)
    want = leaf_histogram_scatter(*args, num_bins=num_bins)
    kw = {"col_pads": pads} if pads else {}
    got = leaf_histogram_onehot(*args, num_bins=num_bins, chunk=chunk,
                                hilo=hilo, **kw)
    assert got.shape == want.shape == (len(STORES[store][0]), num_bins, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-4)
    counts = np.asarray(got)[..., 2]
    assert (counts.sum(axis=1) == np.asarray(args[3] == 0).sum()).all()
    if store == "edges":
        assert counts[:, list(EDGES)].all()
    for j, b in enumerate(STORES[store][0]):
        assert not counts[j, b:].any()


# sha256 of the jaxpr text of `_onehot_accumulate(x, w, 63, chunk,
# hilo=hilo)` on a (1000, f) uint8 store and (1000, 3) float32 weights, as
# the store of up to 64 bins traced before the segments existed (jax 0.9.0)
PARENT_JAXPRS = {
    (28, 4096, False): "e4141c566b71b6e9cd9322f09379e46f"
                       "3d5333e2557a45bbf8aac9bf7f5357a1",
    (28, 4096, True): "9a12c652ccd654d452d9b83775901839"
                      "793aa2d1d39eaa5808a6b3b7fd0517d1",
    (28, 256, False): "64fba121055571f6436eed5ee318f943"
                      "13b2356c9759d7371577d0ced6d948d9",
    (28, 256, True): "2ca95ff3e9d9b148c2d485a02391dd29"
                     "7f23258493933fe89fff190954a5b51f",
    (968, 4096, False): "acca5046bd29892f1f0f8a173b6d401e"
                        "ff7c5cef9d2af12e23789491a0a42931",
    (968, 4096, True): "8de307fd85dc69872cd1dfb233e88a87"
                       "28101979efe39d6f25fd4e0ebdf56d28",
    (968, 256, False): "b326a0b72c530a252dbcbd2cca25b0dd"
                       "9f39067ebce7859dcd8f521f63f836f4",
    (968, 256, True): "df27b6f8099c759630a696ede558e897"
                      "230954d969c39d63a17970e475f76692",
}


@pytest.mark.parametrize("f,chunk,hilo", list(PARENT_JAXPRS),
                         ids=["-".join(map(str, k)) for k in PARENT_JAXPRS])
def test_a_63_bin_store_traces_the_program_it_did(f, chunk, hilo):
    """The uniform 63-bin cells (28 and 968 columns) run the program they
    ran: the same jaxpr, so the same compiled step and cache key."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 63, (1000, f)).astype(np.uint8))
    w = jnp.ones((1000, 3), jnp.float32)
    text = str(jax.make_jaxpr(lambda x, w: histogram._onehot_accumulate(
        x, w, 63, chunk, hilo=hilo))(x, w))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_JAXPRS[f, chunk, hilo]
    assert onehot_pass_shape((), 63, f) == (63, 0)


@pytest.mark.parametrize("store", ["expo", "uniform-256"])
def test_no_contraction_takes_a_onehot_wider_than_64(store):
    """The one-hots the pass builds (`iota` of shape (1, 1, width)) are 32
    and 64 wide on Expo's pads, 64 on a uniform 256-bin store."""
    args, num_bins, pads = _store(store, n=600)
    kw = {"col_pads": pads} if pads else {}
    text = str(jax.make_jaxpr(lambda *a: leaf_histogram_onehot(
        *a, num_bins=num_bins, chunk=256, **kw))(*args))
    widths = {int(b) for b in re.findall(r"shape=\(1, 1, (\d+)\)", text)}
    assert widths == ({32, 64} if pads else {64})
    assert max(widths) == SEGMENT_BINS


def test_the_pass_shape_of_expo_and_of_narrow_stores():
    """Expo's two 256-bin groups give eight segments; a store of up to 64
    bins none, whatever its widths."""
    assert onehot_pass_shape(EXPO_PADS, 256, 10) == (64, 8)
    assert onehot_pass_shape((), 256, 6) == (64, 24)
    assert onehot_pass_shape((), 255, 5) == (64, 20)
    assert onehot_pass_shape((32, 64, 32), 64, 3) == (64, 0)
    assert onehot_pass_shape(col_bin_pads((90, 200, 20), 200), 200, 3) == \
        (64, 6)


WAVE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
        "verbose": -1, "tpu_growth": "wave", "tpu_histogram_mode": "pallas_ct",
        "tpu_pallas_interpret": True, "tpu_wave_width": 4}


def _root_pass_records(X, y, **params):
    timers.clear()
    p = dict(WAVE, **params)
    bst = lgb.Booster(p, lgb.Dataset(X, label=y, params=p))
    recs = [r for r in timers.snapshot()
            if r["kind"] == "count" and r["name"] == "root_pass"]
    by_seq = {r["seq"]: r for r in timers.snapshot() if r["kind"] == "span"}
    return bst, recs, by_seq


def test_the_root_pass_record_says_what_was_cut():
    """Twelve one-hot columns bundled into a 13-bin group beside two
    255-bin columns: one `root_pass` record, inside `learner_build`, with
    the widest one-hot 64 and the eight segments of the two wide columns;
    the same table at 63 bins writes none."""
    rng = np.random.default_rng(1)
    level = rng.integers(0, 12, 3000)
    X = np.concatenate([np.eye(12)[level], rng.standard_normal((3000, 2))],
                       axis=1).astype(np.float32)
    y = ((level % 3 == 0) + 0.3 * X[:, 12] > 0.5).astype(np.float32)
    bst, recs, by_seq = _root_pass_records(X, y)
    layout = bst._gbdt.train_data.bundle
    assert sorted(layout.num_group_bins) == [13, 255, 255]
    (rec,) = recs
    assert rec["fields"] == {"widest": 64, "segments": 8}
    assert by_seq[rec["cause"]]["name"] == "learner_build"
    _, recs, _ = _root_pass_records(X, y, max_bin=63)
    assert recs == []


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


def test_expo_root_compiles_for_a_v5e_with_no_256_bin_onehot(topo):
    """Compiled for a described v5e at 8,388,608 rows, Expo's root takes
    its two 256-bin groups as eight segments in the one 64-bin one-hot of
    the chunk, beside the 32-bin one, and still hands back (10, 256, 3)."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    n, f = 8_388_608, len(EXPO_PADS)

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one)

    def root(x, lid, g, h):
        return leaf_histogram_onehot(x, g, h, lid, 0, None, num_bins=256,
                                     col_pads=EXPO_PADS)

    text = jax.jit(root).trace(
        shape((n, f), jnp.uint8), shape((n,), jnp.int32),
        shape((n,), jnp.float32), shape((n,), jnp.float32)).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "f32[%d,256,3]" % f in text
    onehots = set(re.findall(r"pred\[16384,(\d+),(\d+)\]", text))
    assert onehots == {("4", "32"), ("12", "64")}
