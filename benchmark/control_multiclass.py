"""`control.py` for a multiclass cell: the control and the faults at the
cell's own size, on the chip.

    python3 benchmark/control_multiclass.py --workload covtype_54_train --seeds 11,12

Everything is `control.py`'s (its `read_seed`, the lines it prints, the
float8 control and the faults of `faults.FAULTS`) but two things.  The
data: `control.read_seed` writes a seed's directory with `gen.generate`;
here it is the configuration's own generator, which the cell's driver
runs.  And `half_batch`: `faults.half_batch` zeroes every odd row of the
binary objective's gradients, which a multiclass booster never calls;
here the softmax's gradients lose every odd row, in every class.
"""
import importlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import control, faults  # noqa: E402


class _Generator:
    """`gen.generate`'s place in `control.read_seed`: the configuration's
    own generator (`config["generator"]`)."""

    @staticmethod
    def generate(config, seed, out_dir):
        module = importlib.import_module("benchmark." + config["generator"])
        return module.generate(config, seed, out_dir)


def half_batch():
    """Every odd row left out of every class's histograms: its gradient
    and hessian zeroed where the softmax makes them."""
    import jax.numpy as jnp
    from lightgbm_tpu.objectives import MulticlassSoftmax

    orig = MulticlassSoftmax.get_gradients

    def get_gradients(self, score):
        g, h = orig(self, score)
        # (k x N,) flat, class-major: a row's parity is its index's mod N
        keep = (jnp.arange(g.shape[0]) % self.num_data) % 2 == 0
        return jnp.where(keep, g, 0.0), jnp.where(keep, h, 0.0)

    return faults._patched(MulticlassSoftmax, "get_gradients", get_gradients)


def main(argv=None):
    control.gen = _Generator
    faults.FAULTS = dict(faults.FAULTS, half_batch=half_batch)
    control.main(argv)


if __name__ == "__main__":
    main()
