"""The program's own spans on the device trace's clock: each idle gap of a
device named by the program span it falls in.

``lightgbm_tpu/obs/timers.py span`` enters a
``jax.profiler.TraceAnnotation("lgbm_<name>")`` around every span of its
ring, so inside a profiler window the program's spans (``lgbm_iteration``
and, on the staged chain, its ``lgbm_gradients``, ``lgbm_grow_args``,
``lgbm_dispatch`` and ``lgbm_score_update``) sit on the host plane's
thread lines beside the benchmark's ``bench_`` spans and on the clock of
the device planes.  `benchmark/trace.py` keeps the ``bench_`` spans
alone; this module adds the program's, and leaves every number of
`trace.reduce_planes` as it was:

program_spans  [(name, start_s, end_s)] of every host event whose name
               starts ``lgbm_``, a key of `read_planes`' output
idle_spans     [what the host was doing, seconds]: each idle stretch of
               `trace.reduce_planes`' window on a device, cut at the
               program spans' boundaries; a piece goes to the innermost
               program span open over it (the one that started last), and
               a piece that none covers to the ``bench_`` span covering
               most of it, as `idle_gaps` names a whole gap, or to
               ``no bench span``.  Averaged over the device planes as
               `idle_gaps` is, largest first.  Planes without
               ``program_spans`` give `idle_gaps` itself.
"""
import bisect

from benchmark import trace

PROGRAM_PREFIX = "lgbm_"


def program_spans(path):
    """``[(name, start_s, end_s)]`` of the ``lgbm_`` host events of one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


def read_planes(path):
    """`trace.read_planes` of one ``.xplane.pb`` with its
    ``program_spans``."""
    return dict(trace.read_planes(path), program_spans=program_spans(path))


def reduce_planes(planes, chips=1):
    """`trace.reduce_planes` and ``idle_spans``."""
    return dict(trace.reduce_planes(planes, chips),
                idle_spans=idle_spans(planes, chips))


def _innermost(spans):
    """``(bounds, names)``: the sorted boundaries of `spans` and, for each
    stretch between two of them, the name of the innermost span open over
    it, or None."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    names = []
    for lo, hi in zip(bounds, bounds[1:]):
        best = None
        for name, s, e in spans:
            if s <= lo and e >= hi and (best is None
                                        or (s, -e) > (best[1], -best[2])):
                best = (name, s, e)
        names.append(best and best[0])
    return bounds, names


def _pieces(s, e, bounds, names):
    """``[(program span or None, start, end)]`` of the gap [s, e] cut at
    `bounds`."""
    out = []
    at = s
    i = bisect.bisect_right(bounds, s) - 1
    while at < e:
        if i < 0:
            end, name = (bounds[0] if bounds else e), None
        elif i >= len(names):
            end, name = e, None
        else:
            end, name = bounds[i + 1], names[i]
        end = min(end, e)
        if end > at:
            out.append((name, at, end))
        at = max(at, end)
        i += 1
    return out


def idle_spans(planes, chips=1):
    """The device's idle seconds by the span the host was in (module
    docstring)."""
    devices, spans = planes["devices"], planes["spans"]
    program = planes.get("program_spans") or []
    every = [iv for ops in devices.values() for iv in ops]
    if spans:
        lo = min(s for _, s, _ in spans)
        hi = max(e for _, _, e in spans)
    elif every:
        lo = min(s for _, s, _ in every)
        hi = max(e for _, _, e in every)
    else:
        return []
    bounds, names = _innermost(program)
    idle = {}
    for ops in devices.values():
        ivs = [(max(s, lo), min(e, hi)) for _, s, e in ops
               if e > lo and s < hi]
        for s, e in trace.gaps(ivs, lo, hi):
            for name, ps, pe in _pieces(s, e, bounds, names):
                if name is None:
                    name, most = "no bench span", 0.0
                    for bench, bs, be in spans:
                        cover = min(pe, be) - max(ps, bs)
                        if cover > most:
                            name, most = bench, cover
                idle[name] = idle.get(name, 0.0) + (pe - ps)
    n = max(len(devices), chips, 1)
    return sorted(([k, v / n] for k, v in idle.items()),
                  key=lambda kv: -kv[1])
