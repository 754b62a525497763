"""`run.py` with the trace read by `spans.py` as well: a builder's look at
where each cell's device idles, by the program span the host was in.

    python3 benchmark/spans_run.py --workload <cell> --seed <n> --seconds <s> --trace 1

Runs the cell exactly as `run.py` does and prints its result line; with
``--trace 1`` one more JSON line follows, ``{"spans": ...}``:
``idle_spans`` (the first ten), ``idle_gaps``, ``host_gap_ms`` (1000 x
the idle seconds under an ``lgbm_`` span over the window's iterations:
the device idle that the program's own host path causes) and
``traced_iters_per_s`` (iterations over the traced window).  The drivers
put none of it in ``breakdown``: PERF.md section 7 says which lines
would.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run, spans, trace  # noqa: E402


def main(argv=None, devices=None):
    seen = {}

    def reduce_dir(trace_dir, chips=1):
        seen.update(spans.reduce_planes(
            spans.read_planes(trace.find_xplane(trace_dir)), chips))
        return dict(seen)

    kept, trace.reduce_dir = trace.reduce_dir, reduce_dir
    try:
        result = run.main(argv, devices=devices)
    finally:
        trace.reduce_dir = kept
    if seen:
        n = max(result["attempted"], 1)
        program = sum(s for name, s in seen["idle_spans"]
                      if name.startswith(spans.PROGRAM_PREFIX))
        print(json.dumps({"spans": {
            "idle_spans": seen["idle_spans"][:10],
            "idle_gaps": seen["idle_gaps"],
            "host_gap_ms": 1000.0 * program / n,
            "traced_iters_per_s": (n / seen["window_s"]
                                   if seen["window_s"] else None)}}))
    return result, seen


if __name__ == "__main__":
    main()
