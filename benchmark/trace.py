"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device plane
is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
device operation, with a start and a duration in nanoseconds.  Host spans
that the benchmark puts around its own calls
(``jax.profiler.TraceAnnotation``, names starting ``bench_``) sit on the
host plane's thread lines, on the same clock.

busy_s      union of the device-operation intervals inside the window,
            averaged over the device planes used
            (an operation's name is its HLO text; what is kept is the
            instruction's own name, before `` = ``.  A ``while`` holds the
            operations of its body, so every time by name is self time:
            an operation's duration less what runs nested inside it)
window_s    from the first ``bench_`` span's start to the last one's end
            (where there is none: the device events' own extent)
device_ops  [name, seconds] by summed device time, largest first
idle_gaps   [what the host was doing, seconds]: the longest gaps between
            device operations, each named by the ``bench_`` span that
            covers most of it
mosaic_s    summed time of the Mosaic (Pallas) custom calls
hist_kernel_s, hist_kernel_launches
            summed time and count of the wave histogram kernel, where
            the trace tells it apart, else None
"""
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench_"
# how the trace names what the benchmark has to tell apart; a name that
# stops matching leaves its metric out, it never reads 0
MOSAIC_MARKS = ('custom_call_target="tpu_custom_call"',)
HIST_KERNEL_MARKS = ("%wave_histogram", "%wave_partition_hist")


def union_seconds(intervals):
    """Summed length of the union of ``(start, end)`` intervals."""
    total = 0.0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, lo, hi):
    """The idle ``(start, end)`` stretches of [lo, hi] that no interval
    covers."""
    out = []
    at = lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _is(name, marks):
    return any(m in name for m in marks)


def short_name(name):
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops):
    """``[(name, self seconds)]`` of one line's ``(name, start, end)``
    events, where an event may hold others nested inside it."""
    out = []
    stack = []          # [name, end, seconds of children]

    def close():
        name, start, end, inner = stack.pop()
        out.append((name, max(end - start - inner, 0.0)))
        if stack:
            stack[-1][3] += end - start

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][2]:
            close()
        stack.append([name, s, e, 0.0])
    while stack:
        close()
    return out


def read_planes(path):
    """``{"devices": {plane: [(name, start_s, end_s)]}, "spans": [...]}``
    of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
            if ops:
                devices[plane.name] = ops
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"devices": devices, "spans": spans}


def reduce_planes(planes, chips=1):
    """The numbers of the module's docstring from `read_planes`' output."""
    devices, spans = planes["devices"], planes["spans"]
    every = [iv for ops in devices.values() for iv in ops]
    if spans:
        lo = min(s for _, s, _ in spans)
        hi = max(e for _, _, e in spans)
    elif every:
        lo = min(s for _, s, _ in every)
        hi = max(e for _, _, e in every)
    else:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "mosaic_s": None, "hist_kernel_s": None,
                "hist_kernel_launches": None}
    by_name, busy = {}, 0.0
    mosaic = hist = None
    launches = None
    idle = {}
    for ops in devices.values():
        ivs = _clip([(s, e) for _, s, e in ops], lo, hi)
        busy += union_seconds(ivs)
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        for name, d in self_times(inside):
            key = short_name(name)
            by_name[key] = by_name.get(key, 0.0) + d
            if _is(name, MOSAIC_MARKS):
                mosaic = (mosaic or 0.0) + d
            if _is(name, HIST_KERNEL_MARKS):
                hist = (hist or 0.0) + d
                launches = (launches or 0) + 1
        for s, e in gaps(ivs, lo, hi):
            doing, most = "no bench span", 0.0
            for name, ss, se in spans:
                cover = min(e, se) - max(s, ss)
                if cover > most:
                    doing, most = name, cover
            idle[doing] = idle.get(doing, 0.0) + (e - s)
    n = max(len(devices), chips, 1)
    return {"busy_s": busy / n, "window_s": hi - lo,
            "device_ops": sorted(([k, v / n] for k, v in by_name.items()),
                                 key=lambda kv: -kv[1]),
            "idle_gaps": sorted(([k, v / n] for k, v in idle.items()),
                                key=lambda kv: -kv[1]),
            "mosaic_s": None if mosaic is None else mosaic / n,
            "hist_kernel_s": None if hist is None else hist / n,
            "hist_kernel_launches": launches}


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def reduce_dir(trace_dir, chips=1):
    return reduce_planes(read_planes(find_xplane(trace_dir)), chips)
