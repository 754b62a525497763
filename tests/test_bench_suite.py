"""tools/bench_suite.py child protocol: shape generation is process-stable,
the measurement JSON contract holds, and the dataset cache round-trips."""
import contextlib
import io
import json
import os
import zlib

import numpy as np


def test_suite_child_json_contract(monkeypatch):
    import tools.bench_suite as bs

    name = "tinytest"
    monkeypatch.setitem(bs.SHAPES, name, dict(n=6000, f=6, params={
        "objective": "binary", "metric": "auc", "num_leaves": 15,
        "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1},
        warmup=1, measured=2, timeout=300))
    cache = bs.cache_path(name)
    if os.path.exists(cache):
        os.remove(cache)
    try:
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            bs.child(name)
        res = json.loads(buf.getvalue().strip().splitlines()[-1])
        for key in ("dt", "metric", "mode", "growth", "order", "W",
                    "wall"):
            assert key in res, key
        assert res["dt"] > 0 and 0.5 < res["metric"] <= 1.0
        assert os.path.exists(cache)
        # second run loads the cache and must agree on the metric
        buf2 = io.StringIO()
        with contextlib.redirect_stdout(buf2):
            bs.child(name)
        res2 = json.loads(buf2.getvalue().strip().splitlines()[-1])
        assert res2["metric"] == res["metric"]
    finally:
        if os.path.exists(cache):
            os.remove(cache)


def test_suite_shapes_are_process_stable(monkeypatch):
    """The seed must be a stable content hash — Python's salted hash()
    would give the TPU and reference-CLI arms different data.  Pin the
    actual bytes so a regression to hash(name) (stable in-process but
    not across) cannot stay green."""
    import tools.bench_suite as bs
    monkeypatch.setitem(bs.SHAPES, "tiny2", dict(
        n=2000, f=4, params={}, warmup=0, measured=1, timeout=60))
    X, y, _ = bs.make_shape("tiny2")
    rng = np.random.default_rng(zlib.crc32(b"tiny2"))
    w = rng.normal(size=4) * (rng.random(4) > 0.3)
    Xe = rng.normal(size=(2000, 4)).astype(np.float32)
    np.testing.assert_array_equal(X, Xe)
    ye = ((Xe @ w * 0.4 + 0.6 * rng.normal(size=2000)) > 0)
    np.testing.assert_array_equal(y, ye.astype(np.float64))
