"""The runner refuses to measure without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from benchmark.files import ROOT


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, "--workload", "epsilon_2000_train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    proc = _run(ROOT, "benchmark/run.py")
    assert proc.returncode not in (0, None)
    assert "TPU" in proc.stderr and "cpu" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_no_program_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run(tmp_path, "benchmark/run.py")
    assert proc.returncode not in (0, None)
    assert "lightgbm_tpu" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_unknown_workload_names_the_cells():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "nope", "--seed",
         "1", "--seconds", "1"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0 and "epsilon_2000_train" in proc.stderr


def test_environment_of_the_configuration_is_added_not_swapped():
    from benchmark.run import apply_environment

    environ = {"LIBTPU_INIT_ARGS": "--a=1", "OTHER": "kept"}
    config = {"environment": {"LIBTPU_INIT_ARGS": "--b=2 --a=1",
                              "PLAIN": "x"}}
    apply_environment(config, environ)
    apply_environment(config, environ)          # a second call adds nothing
    assert environ == {"LIBTPU_INIT_ARGS": "--a=1 --b=2", "OTHER": "kept",
                       "PLAIN": "x"}
    apply_environment({}, environ)
    assert environ["LIBTPU_INIT_ARGS"] == "--a=1 --b=2"


def test_every_configuration_states_the_scoped_vmem_it_runs_under():
    # the histogram kernels ask Mosaic for 100 MiB of VMEM; XLA has to be
    # told, or it places operands there (PERF.md section 7)
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            flags = json.load(f)["environment"]["LIBTPU_INIT_ARGS"]
        assert "--xla_tpu_scoped_vmem_limit_kib=102400" in flags.split()
