"""Microbench: wave-histogram kernels + XLA variants on the live backend.

Times the histogram op (K children) both ways (pallas v1 row-major,
pallas v2 transposed), the XLA one-hot scan at several chunk sizes, and
the partition-style scan.  Each timing forces a host readback and
subtracts the measured null round-trip latency.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def force(o):
    leaves = jax.tree_util.tree_leaves(o)
    return float(jnp.sum(leaves[0].astype(jnp.float32).ravel()[:8]))


def timeit(fn, *args, reps=8, vary=None, rt=0.0):
    """Per-call force timing minus the null round-trip rt.  vary: index of
    an f32 arg scaled per rep (so no two calls are identical)."""
    scales = [jnp.float32(1.0 + 0.001 * i) for i in range(reps + 1)]

    def call(i):
        a = list(args)
        if vary is not None:
            a[vary] = a[vary] * scales[i]
        return fn(*a)

    force(call(0))
    t0 = time.time()
    for i in range(reps):
        force(call(i + 1))
    return (time.time() - t0) / reps - rt


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 999424
    fc, b, k = 28, 63, 32
    rng = np.random.default_rng(0)
    Xh = rng.integers(0, b, size=(n, fc), dtype=np.uint8)
    X = jnp.asarray(Xh)
    Xt = jnp.asarray(np.ascontiguousarray(Xh.T))
    leaf_id = jnp.asarray(rng.integers(0, 255, size=n, dtype=np.int32))
    w3 = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    cid = jnp.asarray(np.arange(k, dtype=np.int32))

    # null round-trip: same force pattern on a trivial varying op
    z = jnp.ones((8, 8), jnp.float32)
    rt = timeit(jax.jit(lambda a: a * 2.0), z, vary=0)
    print("null round-trip: %.2f ms" % (rt * 1e3), flush=True)

    from lightgbm_tpu.ops.pallas_wave import (wave_histogram_pallas,
                                              wave_histogram_pallas_t)

    t = timeit(jax.jit(lambda x, l, w, c: wave_histogram_pallas(
        x, l, w, c, num_bins=b)), X, leaf_id, w3, cid, vary=2, rt=rt)
    print("pallas v1 (row-major): %.2f ms" % (t * 1e3), flush=True)

    t = timeit(jax.jit(lambda x, l, w, c: wave_histogram_pallas_t(
        x, l, w, c, num_bins=b)), Xt, leaf_id, w3, cid, vary=2, rt=rt)
    print("pallas v2 (transposed): %.2f ms" % (t * 1e3), flush=True)

    for chunk in (2048, 4096, 8192, 16384, 32768):
        if n % chunk:
            continue
        nch = n // chunk

        def xla_hist(X, leaf_id, w3, cid, _c=chunk, _nch=nch):
            xb = X.reshape(_nch, _c, fc)
            lb = leaf_id.reshape(_nch, _c)
            wb = w3.reshape(_nch, _c, 3)

            def step(acc, args):
                xc, lc, wc = args
                match = (lc[:, None] == cid[None, :]).astype(jnp.float32)
                wmat = (match[:, :, None] * wc[:, None, :]).reshape(_c, 3 * k)
                oh = jax.nn.one_hot(xc.astype(jnp.int32), b,
                                    dtype=jnp.bfloat16)
                return acc + jnp.einsum(
                    "cq,cw->qw", oh.reshape(_c, fc * b), wmat,
                    preferred_element_type=jnp.float32), None

            acc, _ = jax.lax.scan(
                step, jnp.zeros((fc * b, 3 * k), jnp.float32), (xb, lb, wb))
            return acc

        t = timeit(jax.jit(xla_hist), X, leaf_id, w3, cid, vary=2, rt=rt)
        print("xla scan hist chunk=%5d: %.2f ms" % (chunk, t * 1e3),
              flush=True)

    tbl = jnp.asarray(rng.normal(size=(255, 10)).astype(np.float32))
    chunk = 16384
    nch = n // chunk

    def part_scan(X, leaf_id, tbl):
        xb = X.reshape(nch, chunk, fc)
        lb = leaf_id.reshape(nch, chunk)
        l_iota = jnp.arange(255, dtype=jnp.int32)
        f_iota = jnp.arange(fc, dtype=jnp.int32)

        def step(_, args):
            xc, lc = args
            leaf_oh = (lc[:, None] == l_iota[None, :]).astype(jnp.float32)
            r = jnp.matmul(leaf_oh, tbl, precision=jax.lax.Precision.HIGHEST)
            cj = r[:, 1].astype(jnp.int32)
            colv = jnp.sum(jnp.where(cj[:, None] == f_iota[None, :], xc, 0)
                           .astype(jnp.int32), axis=1)
            lc2 = jnp.where(colv <= r[:, 2].astype(jnp.int32),
                            lc, r[:, 6].astype(jnp.int32))
            return _, lc2

        _, lid = jax.lax.scan(step, 0, (xb, lb))
        return lid

    if n % chunk == 0:
        t = timeit(jax.jit(part_scan), X, leaf_id, tbl, vary=2, rt=rt)
        print("partition scan chunk=16384: %.2f ms" % (t * 1e3), flush=True)


if __name__ == "__main__":
    main()
