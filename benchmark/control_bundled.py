"""`control.py` for a cell whose store is bundled: the control and the
faults at the cell's own size, on the chip.

    python3 benchmark/control_bundled.py --workload expo_700_train --seeds 11,12

Everything is `control.py`'s (its `read_seed`, the same `faults.FAULTS`,
the same lines printed) but the data: `control.read_seed` writes a seed's
directory with `gen.generate`, which would write the model's 700 columns
dense; here it writes them with `gen_onehot.generate`, the generator the
cell's own driver uses, and hands the directory to that driver.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import control, gen_onehot  # noqa: E402


def main(argv=None):
    control.gen = gen_onehot    # the one name `read_seed` writes data by
    control.main(argv)


if __name__ == "__main__":
    main()
