"""Capture a jax.profiler trace of the wave engine on the current backend.

The flagship residue analysis (ROADMAP.md) needs a ranked breakdown of
where the ~130 ms/wave that is not the histogram kernel goes; no trace
has ever been captured on chip.  This tool trains the bench recipe and
wraps the steady-state iterations in a profiler trace viewable in
Perfetto / TensorBoard.

Usage:  python tools/tpu_profile.py [n_rows] [outdir] [k=v ...]
        # defaults: 1_000_000 /tmp/tpu_trace; k=v pairs override params
        # e.g. python tools/tpu_profile.py 999424 /tmp/tr tpu_wave_chunk=131072
        python tools/tpu_profile.py --shape expo_cat [outdir] [k=v ...]
        # profile a bench_suite shape instead (binned-dataset cache
        # shared with the suite) — e.g. the 3.9x categorical headline
        # (VERDICT r4 weak #7) or a pathological width cell:
        # tools/tpu_profile.py --shape yahoo /tmp/tr tpu_wave_width=32
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _obs_params(outdir):
    """Timeline params for the profiled run: per-iteration fencing plus
    compile-cost capture so the roofline table can be printed next to
    the trace (the trace says WHERE the time goes, the roofline says how
    far each entry sits from the hardware)."""
    os.makedirs(outdir, exist_ok=True)
    return {"obs_events_path": os.path.join(outdir, "obs_timeline.jsonl"),
            "obs_timing": "iter", "obs_compile": True,
            "obs_utilization_every": 1}


def _print_roofline(gbdt, outdir):
    gbdt._obs.close()
    obs_path = os.path.join(outdir, "obs_timeline.jsonl")
    try:
        from lightgbm_tpu.obs import read_events
        from lightgbm_tpu.obs.roofline import render_roofline
        print()
        events = read_events(obs_path)
        render_roofline(events)
        print("timeline written to", obs_path,
              "- rerun the table with: python -m lightgbm_tpu obs "
              "roofline", obs_path)
    except Exception as e:           # the trace must survive a table bug
        print("tpu_profile: roofline table unavailable (%s)" % e,
              file=sys.stderr)
        return
    # the host half of the same window (obs/prof.py): the device trace
    # above shows what the chips ran, this shows what the host was doing
    # between submissions — one command, both halves of the pipeline
    try:
        from lightgbm_tpu.obs.prof import render_top
        print()
        render_top(events, top=10)
        print("full host profile: python -m lightgbm_tpu obs prof %s "
              "--flame %s" % (obs_path,
                              os.path.join(outdir, "flamegraph.html")))
    except Exception as e:
        print("tpu_profile: host top-table unavailable (%s)" % e,
              file=sys.stderr)


def main():
    argv = list(sys.argv[1:])
    shape = None
    if "--shape" in argv:
        i = argv.index("--shape")
        shape = argv[i + 1]
        del argv[i:i + 2]
    args = [a for a in argv if "=" not in a]
    overrides = dict(a.split("=", 1) for a in argv if "=" in a)
    if shape is None:
        n = int(args[0]) if args else 999_424
        outdir = args[1] if len(args) > 1 else "/tmp/tpu_trace"
    else:
        n = 999_424                      # unused; the shape sizes itself
        outdir = args[-1] if args else "/tmp/tpu_trace"

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.common import enable_compilation_cache
    enable_compilation_cache()

    if shape is not None:
        from tools.bench_suite import SHAPES, cached_dataset
        spec = SHAPES[shape]
        train_set = cached_dataset(shape)
        params = dict(spec["params"], verbose=-1, **_obs_params(outdir))
        params.update(overrides)
        train_set.params = dict(train_set.params or {}, **params)
        bst = lgb.Booster(params=params, train_set=train_set)
        gbdt = bst._gbdt
        for _ in range(2):
            gbdt.train_one_iter(None, None, False)
        jax.block_until_ready(gbdt._score_dev)
        with jax.profiler.trace(outdir):
            for _ in range(3):
                gbdt.train_one_iter(None, None, False)
            jax.block_until_ready(gbdt._score_dev)
        print("trace written to", outdir)
        _print_roofline(gbdt, outdir)
        return

    from tools.bench_modes import make_data
    X, y = make_data(n)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 1, "verbose": -1,
              "metric": "auc", "tpu_growth": "wave", "tpu_wave_width": 32}
    params.update(_obs_params(outdir))
    params.update(overrides)
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, label=y, params=params))
    gbdt = bst._gbdt
    for _ in range(3):                      # compile + warm
        gbdt.train_one_iter(None, None, False)
    jax.block_until_ready(gbdt._score_dev)

    with jax.profiler.trace(outdir):
        for _ in range(3):
            gbdt.train_one_iter(None, None, False)
        jax.block_until_ready(gbdt._score_dev)
    print("trace written to", outdir,
          "- open the .trace.json.gz in Perfetto (ui.perfetto.dev) or "
          "point TensorBoard's profile plugin at the directory")
    _print_roofline(gbdt, outdir)


if __name__ == "__main__":
    main()
