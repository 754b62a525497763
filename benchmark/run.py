"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
BENCHMARK.json gives it: ``configs/<config>.json`` (the entry's `file`),
``traffic/<traffic>.json``, ``metrics/<metric>.py``.  A traffic file names
its driver, ``drivers/<driver>.py``, which does the run; a configuration
names its plain reference, ``references/<reference>.py``.  Adding a cell
or a metric adds files and entries and edits none.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``checks``: every number that decided
``correct`` beside its limit.  The same numbers are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
import time

T0 = time.time()        # set-up counts from here

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.files import HERE, ROOT, load_json, load_module  # noqa: E402


def resolve_cell(bench, workload):
    """The cell's entry, its configuration (entry and file) and traffic."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit("unknown workload %r; BENCHMARK.json has: %s"
                         % (workload, ", ".join(sorted(cells))))
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, entry, config, traffic


def apply_environment(config, environ=os.environ):
    """Put the configuration's `environment` in place, before JAX starts.

    Flag lists (``LIBTPU_INIT_ARGS``, ``XLA_FLAGS``) are added to what the
    machine already sets; any other variable is set outright.  It is part
    of the deployment the configuration states: the program runs under it
    in every run, the control's and the faults' too.
    """
    for name, value in (config.get("environment") or {}).items():
        if name in ("LIBTPU_INIT_ARGS", "XLA_FLAGS"):
            have = environ.get(name, "").split()
            environ[name] = " ".join(
                have + [f for f in value.split() if f not in have])
        else:
            environ[name] = value


def metric_names(bench, section, workload, end_to_end_reported=None):
    """Names of `section`'s metrics that this cell reports."""
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or end_to_end_reported is None \
                or m["moves"] in end_to_end_reported:
            out.append(m)
    return out


def require_chips(chips):
    """The devices, or exit: a measurement never falls back to the CPU."""
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu" or len(devices) < chips:
        sys.stderr.write(
            "benchmark needs %d TPU chip(s); JAX found backend %r with %d "
            "device(s): no result\n" % (chips, backend, len(devices)))
        raise SystemExit(3)
    return devices


def per_layer_values(bench, workload, end_to_end_reported, run):
    """Each per-layer metric's reader over the run; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metric_names(bench, "per_layer", workload, end_to_end_reported):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, devices=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "lightgbm_tpu")):
        sys.stderr.write("the program (lightgbm_tpu/) is not in %s: "
                         "no result\n" % ROOT)
        raise SystemExit(4)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _, config, traffic = resolve_cell(bench, args.workload)
    apply_environment(config)
    if devices is None:
        devices = require_chips(int(cell["chips"]))
    driver = load_module("drivers", traffic["driver"])
    out = driver.run({
        "t0": T0, "cell": cell, "config": config, "traffic": traffic,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "devices": devices[:int(cell["chips"])], "root": ROOT})
    end_to_end = {m["name"]: m for m in
                  metric_names(bench, "end_to_end", args.workload)}
    if args.trace:
        metrics = per_layer_values(bench, args.workload, set(end_to_end),
                                   out["run"])
    else:
        metrics = {name: {"value": float(out["end_to_end"][name]),
                          "unit": m["unit"]}
                   for name, m in end_to_end.items()}
    checks = out["checks"]
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]),
              "metrics": metrics, "device": out["device"]}
    if args.trace and out.get("breakdown"):
        result["breakdown"] = out["breakdown"]
    result["checks"] = checks
    sys.stdout.flush()
    for name, pair in checks.items():
        sys.stderr.write("check %s %r limit %r\n"
                         % (name, pair["value"], pair["limit"]))
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return result


if __name__ == "__main__":
    main()
