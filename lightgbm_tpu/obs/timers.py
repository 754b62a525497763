"""Phase clocks and per-entry-point timers for the training loop.

JAX dispatch is asynchronous: a host-side ``time.perf_counter()`` around a
jitted call measures dispatch cost, not device time.  Device-accurate
timing requires fencing the result with ``jax.block_until_ready`` — which
also breaks the async pipeline, so every timer here takes fencing as a
parameter and the caller (RunObserver) decides per the ``obs_timing``
mode.  All timers are plain-Python and allocation-light; none of them is
on the disabled path (NULL_OBSERVER never constructs one).

The span ring at the end of this module is the one part that is always
on: ``span``/``count`` records from inside the program (set-up, one
iteration, one tree's grow counters), the step program's table from HLO
instruction name to ``jax.named_scope``, and the fold from a device
trace's times by name to seconds per scope.  It never fences and it
changes no program: docs/Observability.md "Spans, scopes and counters".
"""
from __future__ import annotations

import collections
import itertools
import json
import re
import threading
import time

# process-global count of host<->device synchronizations issued through
# fence().  Every deliberate block_until_ready in the training stack
# routes through fence() so this is a complete audit: a default run
# (NULL observer) must leave it unchanged across
# training — asserted by bench.py --dry.
_FENCE_COUNT = 0


def fence_count() -> int:
    """Total fence()/fenced_get() syncs issued (sync audit)."""
    return _FENCE_COUNT


def fence(value):
    """Block until ``value`` (array / pytree / None) is device-complete.

    None-safe and forgiving: values that are not JAX types (python
    scalars, numpy arrays) pass through untouched, so call sites can hand
    over whatever the phase produced without type checks.
    """
    global _FENCE_COUNT
    if value is None:
        return
    try:
        import jax
        _FENCE_COUNT += 1
        jax.block_until_ready(value)
    except Exception:       # non-jax value, or backend already torn down
        pass


def fenced_get(value):
    """``jax.device_get`` that counts itself in the sync audit.

    The counted twin of ``fence()`` for readbacks that need the host
    value, not just completion: tree materialization, the periodic
    stop check, prediction drains.  A bare ``jax.device_get`` on the
    hot path is invisible to ``fence_count()`` (and flagged by the
    ``sync-device-get`` lint rule); this is the sanctioned spelling.
    Non-jax values pass through ``jax.device_get`` unchanged, so call
    sites need no type checks.
    """
    global _FENCE_COUNT
    import jax
    _FENCE_COUNT += 1
    return jax.device_get(value)


class PhaseClock:
    """Splits one iteration into named laps (boost / grow / partition /
    update / eval) and accumulates per-phase totals across iterations.

    ``begin()`` starts the iteration, ``lap(name, value)`` closes the
    current phase (optionally fencing ``value`` first), ``end(value)``
    closes the iteration and returns ``(total_s, {phase: s})``.  Repeated
    laps with the same name within one iteration accumulate (the tree
    loop laps "grow" once per tree).

    ``current`` is the name of the lap most recently crossed within the
    in-flight iteration (None between iterations) — the phase tag the
    sampling profiler (obs/prof.py) stamps on samples.  A plain
    attribute written by the training thread and read racily by the
    sampler: a torn read mis-tags one sample, which the window
    aggregate does not care about.
    """

    def __init__(self, fence_laps=True):
        self.fence_laps = bool(fence_laps)
        self.current = None         # last lap crossed, None between iters
        self._totals = {}           # phase -> cumulative seconds, all iters
        self._phases = {}           # phase -> seconds, current iteration
        self._t_begin = 0.0
        self._t_last = 0.0

    def begin(self):
        self._phases = {}
        self.current = None
        self._t_begin = self._t_last = time.perf_counter()

    def lap(self, name, value=None):
        if self.fence_laps:
            fence(value)
        now = time.perf_counter()
        self._phases[name] = self._phases.get(name, 0.0) + (now - self._t_last)
        self._t_last = now
        self.current = name

    def end(self, value=None):
        fence(value)
        self.current = None
        now = time.perf_counter()
        total = now - self._t_begin
        # time since the last lap (or begin) that no lap() claimed
        tail = now - self._t_last
        if tail > 0.0 and self._phases:
            self._phases["other"] = self._phases.get("other", 0.0) + tail
        phases = self._phases
        self._phases = {}
        for k, v in phases.items():
            self._totals[k] = self._totals.get(k, 0.0) + v
        return total, phases

    def totals(self):
        return dict(self._totals)


class EntryTimers:
    """Compile-vs-execute split per jitted entry point.

    The first fenced call of a jitted function pays trace + XLA compile
    (+ one execute); steady-state calls pay execute only.  ``record``
    returns True exactly once per entry name — the caller emits a
    ``compile`` event for that call — and folds every later call into
    execute statistics.
    """

    def __init__(self):
        self._entries = {}   # name -> stats dict

    def record(self, name, dt):
        st = self._entries.get(name)
        if st is None:
            self._entries[name] = {"first_s": dt, "exec_n": 0,
                                   "exec_total_s": 0.0, "exec_min_s": 0.0,
                                   "exec_max_s": 0.0}
            return True
        st["exec_n"] += 1
        st["exec_total_s"] += dt
        if st["exec_n"] == 1 or dt < st["exec_min_s"]:
            st["exec_min_s"] = dt
        if dt > st["exec_max_s"]:
            st["exec_max_s"] = dt
        return False

    def summary(self):
        out = {}
        for name, st in self._entries.items():
            n = st["exec_n"]
            out[name] = {
                "first_s": st["first_s"],
                "exec_n": n,
                "exec_total_s": st["exec_total_s"],
                "exec_mean_s": (st["exec_total_s"] / n) if n else 0.0,
                "exec_min_s": st["exec_min_s"],
                "exec_max_s": st["exec_max_s"],
                # compile estimate: first call minus a steady-state execute
                "compile_est_s": max(0.0, st["first_s"] -
                                     ((st["exec_total_s"] / n) if n
                                      else 0.0)),
            }
        return out


class OrchestrationClock:
    """Host time BETWEEN device program submissions within one iteration.

    Construction marks the iteration start; ``enter()``/``exit()``
    bracket each device-entry dispatch (the jitted call itself, which is
    asynchronous — its wall time is queueing, not orchestration); the
    remainder is the host's own per-iteration glue: gradient reshapes,
    padding, ``.at[].set`` staging, bookkeeping Python.  That remainder
    is the ``host_orchestration_s`` field on the schema-11 ``iter``
    event — the quantity the fused iteration (ops/fused_iter.py) is
    built to drive to ~0.  Never fences: measuring must not perturb the
    async pipeline.
    """

    __slots__ = ("_t0", "_t_enter", "_inside")

    def __init__(self):
        self._t0 = time.perf_counter()
        self._t_enter = 0.0
        self._inside = 0.0

    def enter(self):
        self._t_enter = time.perf_counter()

    def exit(self):
        self._inside += time.perf_counter() - self._t_enter

    def host_seconds(self) -> float:
        """Elapsed since construction minus time spent inside dispatches."""
        return max(0.0, (time.perf_counter() - self._t0) - self._inside)


# --------------------------------------------------------------------------
# The span ring: always on, process-global, bounded.  Not owned by a
# booster, so a reader that runs after ``del bst`` still finds the run.

RING_SIZE = 4096
# the jax.named_scope names inside the step program (ops/fused_iter.py,
# ops/wave.py); an HLO instruction belongs to the first of these found in
# its op_name, or to UNSCOPED
SCOPES = ("gradients", "root_histogram", "wave_partition", "wave_compact",
          "wave_histogram", "hist_allreduce", "split_search", "tree_commit",
          "score_update", "bundle_view")
# scopes opened INSIDE another declared scope: an instruction under one of
# these belongs to it and not to the scope around it (`bundle_view`, the
# EFB view of ops/grow.py feature_hist_view, sits inside `split_search`,
# whose seconds therefore do not hold it)
INNER_SCOPES = ("bundle_view",)
UNSCOPED = "unscoped"
# the grow loop's counter vector (ops/wave.py), in order.  On the device
# `kernel_rows` holds what the row-slab launches visited (`compacted`
# waves; under a mesh every shard's launches, summed) and
# `slab_rows_moved` the rows the slabs' moves gathered for them, by
# chunks up to the last live row (ops/wave.py move_rows: at most a chunk
# a slab over `kernel_rows`); a tree's record
# in the ring (models/gbdt.py) adds the other waves' `rows` each and
# ``rows_visited = rows + kernel_rows``, as host integers: waves x rows
# passes int32 at real sizes.  `allreduce_words`
# counts array elements, from the operands' shapes, that one shard of a
# mesh hands to `psum` (0 on one device); the record turns them into
# ``allreduce_bytes`` and adds ``shards``, the mesh's devices
COUNTERS = ("waves", "slots", "attempted", "committed", "hist_rows", "rows",
            "kernel_rows", "slab_rows_moved", "compacted", "allreduce_words")
# jax.monitoring durations that become child spans of the open span
_JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

_ring = collections.deque(maxlen=RING_SIZE)
_ring_lock = threading.Lock()   # appends, and every walk over the ring
_seq = itertools.count(1)
_open = threading.local()       # .stack: this thread's open span records
_scopes = {}                    # HLO module name -> {instruction: scope}
_annotation = None              # jax.profiler.TraceAnnotation, once hooked


def _hook_jax():
    """Resolve TraceAnnotation and register the one duration listener, on
    the first span (importing this module touches nothing)."""
    global _annotation
    import jax
    from jax import monitoring
    with _ring_lock:
        if _annotation is None:
            monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            _annotation = jax.profiler.TraceAnnotation
    return _annotation


def _stack():
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _on_jax_duration(event, seconds, fun_name=None, **_kw):
    """A trace / lower / compile / cache-load that JAX reports in a thread
    with an open span becomes that span's child: it ends now and started
    ``seconds`` ago.  JAX reports the inner of two nested intervals first
    (a jit traced inside a jit, the cache load inside the compile), so
    the later, outer one adopts what it covers and replaces what it
    covers of its own name: one ``trace`` for a step, not one a primitive."""
    name = _JAX_DURATIONS.get(event)
    stack = _stack()
    if name is None or not stack:
        return
    parent = stack[-1]
    t1 = time.perf_counter_ns()
    t0 = t1 - int(seconds * 1e9)
    rec = {"kind": "span", "name": name, "seq": next(_seq),
           "cause": parent["seq"], "t0": t0, "t1": t1,
           "ids": dict(parent["ids"])}
    if fun_name is not None:
        rec["ids"]["entry"] = str(fun_name)
    with _ring_lock:
        kept = []
        while _ring and _ring[-1]["kind"] == "span" \
                and _ring[-1]["t1"] >= t0:
            older = _ring.pop()
            if older["cause"] == parent["seq"] and older["t0"] >= t0:
                if older["name"] == name:
                    continue    # a jit traced inside this one: covered
                older["cause"] = rec["seq"]
            kept.append(older)
        _ring.extend(reversed(kept))
        _ring.append(rec)


class span:
    """``with span("upload", shard=3): ...`` records name, start and end
    (``perf_counter_ns``), the span open in this thread when it started
    (``cause``), a process-wide ``seq`` and its ids; ids are inherited, so
    the spans of one iteration share ``it``.  It also enters a
    ``TraceAnnotation("lgbm_<name>", seq=...)``: inside a profiler window
    the span lands on the xplane's host lines, on the device trace's
    clock, and ``seq`` ties it to its ring record."""

    __slots__ = ("rec", "_ann")

    def __init__(self, name, **ids):
        self.rec = {"kind": "span", "name": name, "seq": 0, "cause": None,
                    "t0": 0, "t1": 0, "ids": ids}

    def __enter__(self):
        rec, stack = self.rec, _stack()
        if stack:
            parent = stack[-1]
            rec["cause"] = parent["seq"]
            rec["ids"] = dict(parent["ids"], **rec["ids"])
        rec["seq"] = next(_seq)
        ann = _annotation or _hook_jax()
        self._ann = ann("lgbm_" + rec["name"], seq=rec["seq"], **rec["ids"])
        self._ann.__enter__()
        stack.append(rec)
        rec["t0"] = time.perf_counter_ns()
        return rec

    def __exit__(self, *exc):
        rec = self.rec
        rec["t1"] = time.perf_counter_ns()
        _stack().pop()
        self._ann.__exit__(*exc)
        with _ring_lock:
            _ring.append(rec)
        return False


def count(name, **fields):
    """One counter record (``kind="count"``) at this instant, caused by
    the span open in this thread."""
    stack = _stack()
    rec = {"kind": "count", "name": name, "seq": next(_seq),
           "cause": stack[-1]["seq"] if stack else None,
           "t": time.perf_counter_ns(), "fields": fields}
    with _ring_lock:
        _ring.append(rec)


def snapshot():
    """The ring's records, oldest first (copies: safe to keep)."""
    with _ring_lock:
        return [dict(r) for r in _ring]


def clear():
    """Empty the ring.  Scope tables stay: they describe programs that are
    still loaded."""
    with _ring_lock:
        _ring.clear()


def self_seconds(records):
    """``{seq: seconds}`` for the spans among `records`: a span's duration
    less the part of it that its child spans cover."""
    spans = [r for r in records if r["kind"] == "span"]
    kids = collections.defaultdict(list)
    for r in spans:
        kids[r["cause"]].append((r["t0"], r["t1"]))
    out = {}
    for r in spans:
        covered, end = 0, r["t0"]
        for t0, t1 in sorted(kids.get(r["seq"], ())):
            t0, t1 = max(t0, end), min(t1, r["t1"])
            if t1 > t0:
                covered += t1 - t0
                end = t1
        out[r["seq"]] = (r["t1"] - r["t0"] - covered) * 1e-9
    return out


_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def _scope_of(op_name):
    parts = op_name.split("/")
    for part in parts:
        # inside a vmap the name stack says ``vmap(bundle_view)``
        inner = part.rsplit("(", 1)[-1].rstrip(")")
        if inner in INNER_SCOPES:
            return inner
    for part in parts:
        if part in SCOPES:
            return part
    return None


def scope_table(hlo_text):
    """``(module name, {instruction name: scope})`` of one optimised HLO
    module's text.  An instruction takes the first declared scope in its
    own ``op_name``; a fusion whose own metadata names none takes the
    first one found inside the computation it calls.  Instructions with
    neither are left out (they fold into UNSCOPED)."""
    module, table = None, {}
    inside = {}                 # computation -> first scope seen inside it
    calls = {}                  # unscoped instruction -> computation called
    computation = None
    for line in hlo_text.splitlines():
        if module is None:
            m = _HLO_MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            m = _HLO_COMPUTATION.match(line)
            if m:
                computation = m.group(1)
            continue
        op = _HLO_OP_NAME.search(line)
        scope = _scope_of(op.group(1)) if op else None
        if scope is not None:
            table[m.group(1)] = scope
            inside.setdefault(computation, scope)
        else:
            called = _HLO_CALLS.search(line)
            if called:
                calls[m.group(1)] = called.group(1)
    for name, called in calls.items():
        if called in inside:
            table[name] = inside[called]
    return module, table


def register_device_scopes(hlo_text):
    """Keep the scope table of a step program that was just compiled or
    loaded (ops/fused_iter.py); returns the module's name."""
    module, table = scope_table(hlo_text)
    if module is not None:
        _scopes[module] = table
    return module


def scoped_executable(jitted, cache, args):
    """`jitted` compiled ahead of time for `args`, one executable per
    argument signature, kept in `cache`.  The first call with a signature
    lowers and compiles (or loads from the compile cache) exactly as the
    jitted call would; holding the Compiled is what lets its own HLO text
    be read, at no second compile, for the instruction-to-scope table."""
    import jax
    key = jax.tree_util.tree_structure(args), tuple(
        (a.shape, a.dtype, getattr(a, "weak_type", False))
        for a in jax.tree_util.tree_leaves(args))
    compiled = cache.get(key)
    if compiled is None:
        compiled = jitted.lower(*args).compile()
        register_device_scopes(compiled.as_text())
        cache[key] = compiled
    return compiled


def device_scopes():
    """``{HLO module name: {instruction name: scope}}`` of every step
    program registered in this process."""
    return {m: dict(t) for m, t in _scopes.items()}


def device_time_by_scope(device_ops, table):
    """Fold ``[instruction name, seconds]`` pairs (a device trace's self
    times by short name) into ``{scope: seconds}`` by one module's table;
    what the table does not name is UNSCOPED."""
    out = {}
    for name, seconds in device_ops:
        scope = table.get(name, UNSCOPED)
        out[scope] = out.get(scope, 0.0) + float(seconds)
    return out


def write_spans(path):
    """The ring and the scope tables as one JSON file: what a profiler
    window writes beside its xplane (obs/profile.py)."""
    with open(path, "w") as f:
        json.dump({"records": snapshot(), "scopes": device_scopes(),
                   "declared_scopes": list(SCOPES)}, f)
