"""Read the control and the faults at a cell's own size, on the chip.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13

For each seed it writes the cell's data once and drives the cell's own
driver over it with no measured window (training's readings need none):
first as the program is, then under each of ``faults.py``'s breaks; a
name of the form ``key=value`` is no break but the program run with that
parameter (``tpu_hist_precision=hilo``: is a gap the stated precision's?).  One
JSON line a reading; the last line sums up, per number, the largest sound
reading and the smallest under each break.  Not part of a benchmark run:
the limits in the configurations' files were set from what this printed.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import faults, gen  # noqa: E402
from benchmark.files import ROOT, load_json, load_module  # noqa: E402
from benchmark.run import (apply_environment, require_chips,  # noqa: E402
                           resolve_cell)


def read_seed(cell, config, traffic, seed, devices, names, log=print):
    """``{variant: {number: value}}`` for one seed's data."""
    driver = load_module("drivers", traffic["driver"])
    out = {}
    data_dir = tempfile.mkdtemp(prefix="control_")
    try:
        gen.generate(config, seed, data_dir)
        for name in ["sound"] + list(names):
            ctx = {"t0": time.time(), "cell": cell, "config": config,
                   "traffic": traffic, "seed": seed, "seconds": 0.0,
                   "trace": False, "devices": devices, "root": ROOT,
                   "data_dir": data_dir}
            if name == "sound":
                res = driver.run(ctx, log=log)
            elif "=" in name:
                key, value = name.split("=", 1)
                ctx["config"] = dict(config, params=dict(config["params"],
                                                         **{key: value}))
                res = driver.run(ctx, log=log)
            else:
                try:
                    with faults.FAULTS[name]():
                        res = driver.run(ctx, log=log)
                except Exception as exc:    # a break that crashes has failed
                    log("%s raised %r" % (name, exc))
                    out[name] = {"correct": False, "raised": 1.0}
                    continue
            out[name] = {"correct": res["correct"],
                         **{k: v["value"] for k, v in res["checks"].items()}}
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="fp8_products,half_batch,"
                                        "state_unchanged,answer_altered")
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _, config, traffic = resolve_cell(bench, args.workload)
    apply_environment(config)
    devices = require_chips(int(cell["chips"]))[:int(cell["chips"])]
    names = [f for f in args.faults.split(",") if f]
    summary = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        for variant, numbers in read_seed(cell, config, traffic, seed,
                                          devices, names).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": variant, **numbers}), flush=True)
            for k, v in numbers.items():
                if k == "correct":
                    continue
                lo_hi = summary.setdefault(variant, {}).setdefault(k, [v, v])
                lo_hi[0], lo_hi[1] = min(lo_hi[0], v), max(lo_hi[1], v)
    print(json.dumps({"workload": args.workload, "min_max": summary}))


if __name__ == "__main__":
    main()
