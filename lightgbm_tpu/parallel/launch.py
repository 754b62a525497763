"""Subprocess multi-process simulation: real ``jax.distributed`` workers.

``run_ranks`` (comm.py) simulates ranks on threads — one process, one
backend, host barriers only.  This module is the other half of the PR-4
fixture: ``run_ranks_subprocess`` drives N REAL processes, each with its
own CPU backend and its own ``jax.distributed.initialize`` handshake, so
the exact code path a TPU pod runs (process bootstrap → global mesh →
cross-process psum) is exercised in CI with no pod.  The moral
equivalent of the reference running `mpirun -np 2` on localhost
(.travis.yml:45-52) instead of only linking against MPI.

Worker protocol: the child bootstraps via ``distributed_init()`` (env
autodetect — the parent exports ``JAX_COORDINATOR_ADDRESS`` /
``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` exactly like a pod launcher),
imports ``spec`` ("package.module:function"; callables can't cross a
process boundary), calls ``fn(comm, payload)`` and prints its
JSON-serializable return as a final ``MPRESULT {...}`` line.  The PR-4
``LGBM_MP_*`` fault hooks ride through the inherited environment.

This launcher is a CPU fixture for ``jax.distributed`` and cannot reach
a chip: every rank is forced onto the CPU platform (``_worker_env``,
``_child``), and a chip belongs to one process at a time anyway.  On a
four-chip host the supported layout is ONE process driving the four
devices (``tree_learner=data`` under ``lgb.train``, parallel/mesh.py).

jaxlib's CPU client only grew cross-process collectives in some builds;
on hosts without them workers die with "Multiprocess computations
aren't implemented" and the launcher raises ``MultiprocessUnsupported``
so tests skip instead of fail — same contract as
tests/test_multiprocess.py always had.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))

# jaxlib messages that mean "this CPU client cannot do cross-process
# collectives at all" — an environment limitation, not a code failure
_UNSUPPORTED_MARKERS = (
    "Multiprocess computations aren't implemented",
    "multiprocess computations aren't implemented",
)

DEFAULT_WORKER_TIMEOUT = 540.0


class MultiprocessUnsupported(RuntimeError):
    """The installed jaxlib CPU client lacks cross-process collectives."""


class RankFailure(RuntimeError):
    """One or more worker processes died.  Carries everything the
    elastic driver (parallel/elastic.py) needs to shrink and resume:
    which ranks failed, every rank's exit code, and the per-rank output
    tails (where the watchdog flight-record paths land)."""

    def __init__(self, failed, returncodes, tails, results=None):
        self.failed = sorted(failed)
        self.returncodes = dict(returncodes)
        self.tails = dict(tails)
        self.results = dict(results or {})
        super().__init__(
            "worker rank(s) %s died (exit %s); %d/%d ranks returned"
            % (self.failed,
               {r: self.returncodes.get(r) for r in self.failed},
               len(self.results), len(returncodes)))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(coordinator, size, rank, local_devices, extra_env):
    env = dict(os.environ)
    # the child must see exactly `local_devices` CPU devices, whatever
    # the parent's (test-harness) XLA_FLAGS said
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=%d"
                 % int(local_devices))
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    # the pod-launcher contract distributed_init() autodetects from
    env["JAX_COORDINATOR_ADDRESS"] = coordinator
    env["JAX_NUM_PROCESSES"] = str(int(size))
    env["JAX_PROCESS_ID"] = str(int(rank))
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update({str(k): str(v) for k, v in extra_env.items()})
    return env


def _tail(path, nbytes=8192):
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - nbytes))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


def _parse_result(out):
    """Last MPRESULT line of a worker log, or None."""
    for line in reversed(out.splitlines()):
        if line.startswith("MPRESULT "):
            return json.loads(line[len("MPRESULT "):])["result"]
    return None


def run_ranks_subprocess(size: int, spec: str, payload: Any = None, *,
                         local_devices: int = 1,
                         timeout: float = DEFAULT_WORKER_TIMEOUT,
                         extra_env: Optional[dict] = None,
                         fail_grace: float = 8.0) -> List[Any]:
    """Drive ``fn(comm, payload)`` for ``size`` real processes over a
    localhost coordinator; per-rank results in rank order.

    ``spec`` is a "package.module:function" import target.  ``payload``
    must be JSON-serializable and reaches every rank verbatim.  A rank
    death raises :class:`RankFailure` after giving surviving ranks
    ``fail_grace`` seconds to notice (watchdog flight records) before
    they are killed; a jaxlib without cross-process CPU collectives
    raises :class:`MultiprocessUnsupported`.
    """
    coordinator = "127.0.0.1:%d" % free_port()
    procs, logs = [], []
    with tempfile.TemporaryDirectory(prefix="lgbm_mp_") as td:
        payload_path = os.path.join(td, "payload.json")
        with open(payload_path, "w") as f:
            json.dump(payload, f)
        for rank in range(size):
            log_path = os.path.join(td, "rank%d.log" % rank)
            logs.append(log_path)
            lf = open(log_path, "wb")
            procs.append((subprocess.Popen(
                [sys.executable, "-m", "lightgbm_tpu.parallel.launch",
                 "--child", spec, payload_path],
                stdout=lf, stderr=subprocess.STDOUT, cwd=REPO_ROOT,
                env=_worker_env(coordinator, size, rank, local_devices,
                                extra_env)), lf))
        deadline = time.time() + float(timeout)
        first_death = None
        try:
            while True:
                codes = [p.poll() for p, _ in procs]
                if all(c is not None for c in codes):
                    break
                dead = [r for r, c in enumerate(codes)
                        if c is not None and c != 0]
                if dead and first_death is None:
                    # a rank died; give survivors a bounded window to
                    # hit their barrier timeout / dump flight records
                    first_death = time.time()
                if first_death is not None and \
                        time.time() - first_death > float(fail_grace):
                    break
                if time.time() > deadline:
                    break
                time.sleep(0.1)
        finally:
            for p, lf in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()
                lf.close()
        outs = [_tail(path, 65536) for path in logs]
        codes = {r: p.poll() for r, (p, _) in enumerate(procs)}
        if any(any(m in out for m in _UNSUPPORTED_MARKERS)
               for out in outs):
            raise MultiprocessUnsupported(
                "jax CPU backend on this host cannot run the "
                "multi-process mesh program")
        results = {r: _parse_result(out) for r, out in enumerate(outs)}
        failed = [r for r, c in codes.items()
                  if c != 0 or results[r] is None]
        if failed:
            raise RankFailure(
                failed, codes, {r: outs[r][-2048:] for r in failed},
                {r: v for r, v in results.items() if v is not None})
        return [results[r] for r in range(size)]


def _child(argv):
    """Worker entry: bootstrap the distributed runtime, run the spec."""
    spec, payload_path = argv
    import jax
    jax.config.update("jax_platforms", "cpu")
    from .comm import JaxProcessComm, distributed_init  # noqa: F401
    comm = distributed_init()
    with open(payload_path) as f:
        payload = json.load(f)
    import importlib
    mod_name, fn_name = spec.split(":")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    result = fn(comm, payload)
    sys.stdout.write("MPRESULT " + json.dumps(
        {"rank": comm.rank, "result": result}) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        sys.exit(_child(sys.argv[2:]))
    sys.exit("usage: python -m lightgbm_tpu.parallel.launch --child "
             "<pkg.mod:fn> <payload.json>")
