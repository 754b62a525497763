"""Programmatic jax.profiler trace windows over configured iterations.

``obs_trace_iters=a:b`` (python-range semantics: start at iteration
``a``, stop after iteration ``b-1``) plus ``obs_trace_dir`` captures a
perfetto trace of exactly the steady-state iterations — no bespoke
profiling script per investigation.  The start/stop calls go through
module-level ``_start_trace``/``_stop_trace`` wrappers so tests can
monkeypatch them and exercise the window logic without a real profiler.
"""
from __future__ import annotations

import os

from . import timers
from ..utils.log import Log

SPANS_FILE = "lgbm_spans.json"


def parse_trace_iters(spec):
    """'a:b' -> (a, b) with 0 <= a < b; '' -> None.  Fatal on malformed
    input — a silently dropped trace window wastes an on-chip run."""
    spec = str(spec or "").strip()
    if not spec:
        return None
    parts = spec.split(":")
    if len(parts) == 2:
        try:
            start, stop = int(parts[0]), int(parts[1])
        except ValueError:
            start = stop = -1
        if 0 <= start < stop:
            return (start, stop)
    Log.fatal("Bad obs_trace_iters %r (expected 'a:b' with 0 <= a < b, "
              "e.g. '10:13')", spec)


def _start_trace(trace_dir):
    import jax
    jax.profiler.start_trace(trace_dir)


def _stop_trace():
    import jax
    jax.profiler.stop_trace()


class TraceWindow:
    """Opens the profiler at iteration ``start`` and closes it after
    iteration ``stop - 1``; one window per run."""

    def __init__(self, iters_spec, trace_dir):
        self.window = parse_trace_iters(iters_spec)
        self.trace_dir = str(trace_dir or "")
        self.active = False
        self.done = False

    def maybe_start(self, it, obs):
        if (self.window is None or self.active or self.done
                or it < self.window[0]):
            return
        try:
            _start_trace(self.trace_dir)
        except Exception as exc:        # profiler busy / unsupported
            Log.warning("obs: could not start profiler trace: %s", exc)
            self.done = True
            return
        self.active = True
        obs.event("trace_window", action="start", dir=self.trace_dir, it=it)

    def maybe_stop(self, it, obs):
        if not self.active or it + 1 < self.window[1]:
            return
        self._stop(obs, it)

    def force_stop(self, obs):
        """Close a window left open at run end (early stop inside it)."""
        if self.active:
            self._stop(obs, -1)

    def _stop(self, obs, it):
        try:
            _stop_trace()
        except Exception as exc:
            Log.warning("obs: could not stop profiler trace: %s", exc)
        self.active = False
        self.done = True
        # the program's own spans, counters and scope tables, beside the
        # xplane that was just written (its lgbm_* host events carry the
        # same ``seq``)
        try:
            os.makedirs(self.trace_dir, exist_ok=True)
            timers.write_spans(os.path.join(self.trace_dir, SPANS_FILE))
        except OSError as exc:
            Log.warning("obs: could not write %s: %s", SPANS_FILE, exc)
        obs.event("trace_window", action="stop", dir=self.trace_dir, it=it)
