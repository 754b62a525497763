"""How ``tiny_v5e_spans.xplane.pb`` was recorded, on a TPU v5e (PR 37):

    chiprun -- python3 benchmark/testdata/record_tiny_spans.py chiprun_out/tiny_spans

4,096 rows x 16 columns, 15 leaves.  Two boosters, each compiled by one
update before the profiler starts; inside it three ``Booster.update()``
calls, each in ``bench_update`` / ``bench_sync`` annotations as the
drivers make them: two on the fused step (``tpu_fused_iter=on``: spans
``lgbm_iteration``, ``lgbm_dispatch``) and one on the staged chain
(``tpu_fused_iter=off``: ``lgbm_gradients``, ``lgbm_grow_args``,
``lgbm_dispatch``, ``lgbm_score_update`` under ``lgbm_iteration``).
The plane ``/host:metadata`` is then dropped: it holds the HLO protos of
the compiled programs (1,664,393 B; no event, and nothing any reader
reads), and the file is 2,361,330 B with it, 2 MB being the limit.
"""
import glob
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def drop_hlo_plane(path, out):
    """`path` without its ``/host:metadata`` plane, written to `out` (the
    protobuf module is TensorFlow's, installed beside JAX)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    keep = [plane for plane in space.planes
            if plane.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)
    with open(out, "wb") as f:
        f.write(space.SerializeToString())


def main(out_dir):
    import jax
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(37)
    X = rng.standard_normal((4096, 16)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.1 * rng.standard_normal(4096) > 0)
    boosters = []
    for fused in ("on", "off"):
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "tpu_fused_iter": fused}
        bst = lgb.Booster(params, lgb.Dataset(
            X, label=y.astype(np.float32), params=params))
        bst.update()
        bst._gbdt._score_dev.block_until_ready()
        boosters.append(bst)
    jax.profiler.start_trace(out_dir)
    for bst in (boosters[0], boosters[0], boosters[1]):
        with jax.profiler.TraceAnnotation("bench_update"):
            bst.update()
        with jax.profiler.TraceAnnotation("bench_sync"):
            bst._gbdt._score_dev.block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    from benchmark import spans
    names = sorted({n for n, _, _ in spans.program_spans(path)})
    print("%s %d B; program spans: %s" % (path, os.path.getsize(path),
                                          names))
    out = os.path.join(out_dir, "tiny_v5e_spans.xplane.pb")
    drop_hlo_plane(path, out)
    print("%s %d B" % (out, os.path.getsize(out)))


if __name__ == "__main__":
    main(sys.argv[1])
