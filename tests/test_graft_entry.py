"""End-to-end test of the driver entry points in __graft_entry__.py.

dryrun_multichip must work from a PARENT process that has NOT forced the
CPU platform — that is exactly how the driver invokes it (a parent that
probed jax.devices() would initialize the default backend and hold the
chip). We therefore spawn a fresh interpreter with a clean environment
(no JAX_PLATFORMS, no device-count override) and call dryrun_multichip(8)
from there; the implementation must re-exec itself onto a virtual 8-device
CPU mesh without ever initializing a backend in that parent.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_from_clean_parent():
    env = dict(os.environ)
    # Simulate the driver's environment: nothing pre-forces CPU.
    env.pop("JAX_PLATFORMS", None)
    env.pop("_LGBM_TPU_DRYRUN_CHILD", None)
    env["XLA_FLAGS"] = ""  # no inherited device-count override
    # Keep the *parent* honest: if it tries to initialize a TPU backend it
    # would die on import in this sandbox anyway; the child must force cpu.
    code = ("import __graft_entry__; __graft_entry__.dryrun_multichip(8); "
            "print('PARENT_OK')")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=570)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = proc.stdout
    assert "PARENT_OK" in out
    assert "dryrun_multichip OK (data-parallel)" in out
    assert "dryrun_multichip OK (feature-parallel)" in out
    assert "dryrun_multichip OK (voting-parallel)" in out
    assert "dryrun_multichip OK (data-parallel wave)" in out
    assert "dryrun_multichip OK (data-parallel sparse)" in out


def test_dryrun_child_guard_runs_inline(monkeypatch):
    # With the child marker set AND the cpu platform forced (the pytest
    # harness does both), dryrun_multichip must run inline — spawning a
    # grandchild is a failure here.
    import subprocess as sp

    import __graft_entry__

    real_run = sp.run

    def _no_spawn(cmd, *a, **k):
        # jax/hardware probes (e.g. lscpu) may legitimately call
        # subprocess.run; only a re-exec of the interpreter is a failure.
        if cmd and cmd[0] == sys.executable:
            raise AssertionError("guarded dryrun spawned a child process")
        return real_run(cmd, *a, **k)

    monkeypatch.setattr(sp, "run", _no_spawn)
    monkeypatch.setenv("_LGBM_TPU_DRYRUN_CHILD", "1")
    __graft_entry__.dryrun_multichip(8)


def test_dryrun_stale_marker_still_reexecs(monkeypatch):
    # A leaked _LGBM_TPU_DRYRUN_CHILD in a process that has NOT forced the
    # cpu platform must NOT run inline (it would touch the default backend);
    # it must fall through to the re-exec path.
    import __graft_entry__

    spawned = {}

    class _Proc:
        returncode = 0

    def _fake_run(cmd, **k):
        spawned["env"] = k["env"]
        return _Proc()

    monkeypatch.setenv("_LGBM_TPU_DRYRUN_CHILD", "1")
    monkeypatch.setattr(__graft_entry__, "_dryrun_impl",
                        lambda n: (_ for _ in ()).throw(
                            AssertionError("ran inline on default backend")))
    monkeypatch.setattr(__graft_entry__, "_cpu_forced", lambda: False)
    import subprocess as sp
    monkeypatch.setattr(sp, "run", _fake_run)
    __graft_entry__.dryrun_multichip(8)
    assert spawned["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in \
        spawned["env"]["XLA_FLAGS"]
