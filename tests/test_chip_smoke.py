"""chip_smoke.py off the chip: the script refuses the CPU backend, and its
phase functions run tiny here with the Pallas kernels in interpret mode,
so chip time is not spent on typos.  ``main()`` itself never runs off-TPU.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROWS, HOLDOUT, LEAVES, WIDTH = 1024, 512, 7, 8
# what `auto` resolves to on the chip, spelled out for the CPU, with the
# kernels interpreted and the device predictor forced
TINY = dict(chip_smoke.FLAGSHIP, num_leaves=LEAVES, tpu_growth="wave",
            tpu_histogram_mode="pallas_ct", tpu_pallas_interpret=True,
            tpu_fused_iter="on", tpu_predict="true")
EXPECT = dict(chip_smoke.EXPECT_SERIAL, wave_width=WIDTH,
              pallas_interpret=True)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_entry_refuses_the_cpu_backend(script):
    r = subprocess.run([sys.executable, os.path.join(REPO, script)],
                       capture_output=True, text=True, timeout=300,
                       cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "'cpu', not tpu" in r.stderr
    # no pass line and no metric line
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


@pytest.fixture(scope="module")
def data():
    X, y, Xh, yh = chip_smoke.make_data(ROWS, chip_smoke.FEATURES, HOLDOUT)
    train_set = lgb.Dataset(X, label=y, params=TINY)
    train_set.construct()
    return X, train_set, (Xh, yh)


@pytest.fixture(scope="module")
def fused_booster(data):
    _, train_set, holdout = data
    return chip_smoke.phase_train(train_set, holdout, TINY, 2, EXPECT)


def test_train_and_staged_phases(data, fused_booster):
    _, train_set, holdout = data
    chip_smoke.phase_staged(train_set, holdout, TINY, 2, EXPECT,
                            fused_booster)
    # the phase checks what `auto` resolved to: another kernel fails it
    with pytest.raises(AssertionError, match="learner resolved to"):
        chip_smoke.phase_train(
            train_set, holdout, dict(TINY, tpu_histogram_mode="pallas_t"),
            1, EXPECT)


def test_kernel_phase_and_its_truncation_guard(monkeypatch):
    chip_smoke.phase_kernels(2048, 12, WIDTH, interpret=True)
    # a bf16 cast that truncates (what Mosaic's own cast does) must fail
    # the phase: that is what the compiled run is there to catch
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops import pallas_wave

    def truncating(wmat):
        return pltpu.bitcast(
            pltpu.bitcast(wmat, jnp.uint32) & jnp.uint32(0xFFFF0000),
            jnp.float32).astype(jnp.bfloat16)
    monkeypatch.setattr(pallas_wave, "_round_bf16", truncating)
    for fn in (pallas_wave.wave_histogram_pallas_t,
               pallas_wave.wave_partition_hist_pallas_ct):
        fn.clear_cache()
    try:
        with pytest.raises(AssertionError, match="kernels:"):
            chip_smoke.phase_kernels(2048, 12, WIDTH, interpret=True)
    finally:
        monkeypatch.undo()
        for fn in (pallas_wave.wave_histogram_pallas_t,
                   pallas_wave.wave_partition_hist_pallas_ct):
            fn.clear_cache()


def test_predict_and_serve_phases(data, fused_booster):
    X, _, _ = data
    chip_smoke.phase_predict(fused_booster, X, 256)
    chip_smoke.phase_serve(fused_booster, X, 6, 64)


def test_cli_phase(tmp_path):
    chip_smoke.phase_cli(str(tmp_path))


def test_multichip_phase(tmp_path):
    """Eight virtual CPU devices, a binned table whose files end inside a
    device's rows: the mesh learner has no interpret switch, so its
    kernels are the XLA engine here; the plain reference follows its
    trees."""
    params = dict(TINY, tpu_histogram_mode="pallas_t")
    chip_smoke.phase_multichip(
        9000, 12, 2500, params, 2,
        dict(chip_smoke.EXPECT_DATA_PARALLEL, wave_width=WIDTH,
             pallas_interpret=True), str(tmp_path))
