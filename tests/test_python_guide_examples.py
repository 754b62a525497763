"""The python-guide examples must run end-to-end (reference parity:
examples/python-guide/*.py are executable documentation).

Each example executes in a child process that pins the CPU platform
before jax initializes a backend, so the suite stays hermetic on
machines that have a chip.
"""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
GUIDE = os.path.join(REPO, "examples", "python-guide")

RUNNER = """
import jax
jax.config.update("jax_platforms", "cpu")
import runpy, sys
runpy.run_path(sys.argv[1], run_name="__main__")
"""


@pytest.mark.parametrize("name", ["simple_example", "advanced_example",
                                  "plot_example", "sklearn_example"])
def test_python_guide_example_runs(name):
    r = subprocess.run(
        [sys.executable, "-c", RUNNER,
         os.path.join(GUIDE, name + ".py")],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert r.returncode == 0, "%s failed:\n%s" % (name, r.stderr[-2000:])
    assert r.stdout.strip(), "%s produced no output" % name
