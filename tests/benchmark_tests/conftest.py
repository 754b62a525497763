"""Helpers for the benchmark's own tests: everything tiny, on the CPU."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def tiny_config():
    """The Epsilon configuration cut to a size a test can hold; every
    other key, the limits among them, as the cell runs it."""
    from benchmark.files import load_json

    config = load_json(os.path.join(ROOT, "benchmark", "configs",
                                    "epsilon_2000.json"))
    config = copy.deepcopy(config)
    config.update(rows=20000, columns=40, shard_rows=8192)
    config["params"]["num_leaves"] = 31
    return config


@pytest.fixture
def tiny_traffic():
    from benchmark.files import load_json

    traffic = load_json(os.path.join(ROOT, "benchmark", "traffic",
                                     "train.json"))
    traffic.update(check_nodes=8, trace_iterations=2)
    return traffic


@pytest.fixture
def drive(tiny_config, tiny_traffic):
    """Drive the rest of a run past the harness's look for a chip."""
    import time

    import jax
    from benchmark.files import load_module

    def go(seed, seconds=0.5, trace=False, config=None):
        driver = load_module("drivers", tiny_traffic["driver"])
        return driver.run({
            "t0": time.time(), "cell": {}, "config": config or tiny_config,
            "traffic": tiny_traffic, "seed": seed, "seconds": seconds,
            "trace": trace, "devices": jax.devices()[:1], "root": ROOT},
            log=lambda *a: None)

    return go
