"""`correct` comes out true for the program as it is, and false under the
control (the histograms' products in float8) and under each fault a
training cell can have, with the rest of the run driven as ever.  The
limits are the cell's own, set on the chip, where the program's bf16
products read far higher than the CPU's float32 does here: so on the CPU
the control is caught by the loss, and the leaves' values only where the
first step is concerned."""
import numpy as np
import pytest

from benchmark import faults
from benchmark.references import gbdt_plain as ref


def _values(out):
    return {k: v["value"] for k, v in out["checks"].items()}


def test_the_program_as_it_is_proves_correct(drive):
    out = drive(seed=2 ** 31 + 5)
    assert out["correct"], _values(out)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) >= {"count_mismatch", "leaf_value_gap_step0",
                                  "score_gap_step0", "split_gain_gap_step0",
                                  "loss_gap",
                                  "compiles_in_window", "failed_iterations"}
    assert out["end_to_end"]["iters_per_s"] > 0
    assert out["end_to_end"]["setup_s"] > 0


@pytest.mark.parametrize("fault,caught_by", [
    ("fp8_products", "loss_gap"),
    ("half_batch", "leaf_value_gap_step0"),
    ("half_batch", "score_gap_step0"),
    ("half_batch", "split_gain_gap_step0"),
    ("state_unchanged", "loss_gap"),
    ("answer_altered", "count_mismatch"),
])
def test_control_and_faults_come_out_not_correct(drive, fault, caught_by):
    with faults.FAULTS[fault]():
        out = drive(seed=9)
    assert not out["correct"], _values(out)
    check = out["checks"][caught_by]
    assert check["value"] > check["limit"], (fault, _values(out))


def test_a_non_finite_score_fails_every_iteration(drive, monkeypatch):
    import lightgbm_tpu as lgb

    orig = lgb.Booster.update

    def update(self, *a, **k):
        out = orig(self, *a, **k)
        self._gbdt._score_dev = self._gbdt._score_dev * float("nan")
        self._gbdt._invalidate_train()
        return out

    monkeypatch.setattr(lgb.Booster, "update", update)
    out = drive(seed=3)
    assert not out["correct"]
    assert out["failed"] == out["attempted"] > 0


# ---- the reference's own arithmetic, by hand

def test_gradients_and_loss_at_score_zero():
    g, h = ref.gradients(np.zeros(4), np.array([0., 1., 1., 0.]))
    assert np.allclose(g, [0.5, -0.5, -0.5, 0.5]) and np.allclose(h, 0.25)
    assert ref.logloss(np.zeros(4), np.array([0., 1., 1., 0.])) == \
        pytest.approx(np.log(2.0))


def test_walk_places_default_bin_rows_where_the_split_says():
    # one column, bins 0..4, default bin 2; split at threshold 1
    shard = np.array([[0, 1, 2, 3, 4, 2]], np.uint8)
    tree = {"num_leaves": 2, "split_feature": [0], "threshold_bin": [1],
            "left_child": [-1], "right_child": [-2]}
    for dbz, want in ((0, [0, 0, 0, 1, 1, 0]), (2, [0, 0, 1, 1, 1, 1]),
                      (4, [0, 0, 1, 1, 1, 1])):
        tree["dbz"] = [dbz]
        assert ref.leaf_of_rows(tree, [shard], 2).tolist() == want


def test_leaves_under_each_split():
    tree = {"num_leaves": 3, "left_child": [1, -1], "right_child": [-2, -3]}
    assert ref.leaves_under(tree).tolist() == [[True, True, True],
                                               [True, False, True]]


def test_split_gains_by_hand():
    # one column, three bins, default bin 1; (g, h) a bin
    hist = np.array([[[2.0, 1.0], [-1.0, 1.0], [-3.0, 2.0]]])
    gains, loose = ref.split_gains(hist, default_bin=1)
    assert np.array_equal(gains, loose)
    parent = (-2.0) ** 2 / 4.0
    # natural place, threshold 0: left (2, 1), right (-4, 3)
    assert gains[1, 0, 0] == pytest.approx(4.0 + 16.0 / 3.0 - parent)
    # default bin placed first, threshold 0: left (1, 2), right (-3, 2)
    assert gains[0, 0, 0] == pytest.approx(0.5 + 4.5 - parent)
    # default bin placed last, threshold 1: left (2, 1), right (-4, 3)
    assert gains[2, 0, 1] == pytest.approx(4.0 + 16.0 / 3.0 - parent)


def test_sides_on_the_hessian_line_go_either_way():
    # left hessian 1.0 against a minimum of 1.0: out of the best, in for a
    # split the program made
    hist = np.array([[[2.0, 1.0], [-1.0, 1.0], [-3.0, 2.0]]])
    strict, loose = ref.split_gains(hist, default_bin=1, min_sum_hessian=1.0)
    assert strict[1, 0, 0] == -np.inf and np.isfinite(loose[1, 0, 0])
    assert np.isfinite(strict[1, 0, 1])


def test_node_histograms_match_a_loop():
    rng = np.random.default_rng(0)
    shards = [rng.integers(0, 5, (3, 50), dtype=np.uint8),
              rng.integers(0, 5, (3, 30), dtype=np.uint8)]
    leaf = rng.integers(0, 3, 80)
    g, h = rng.normal(size=80), rng.uniform(0.1, 1, 80)
    member = np.array([[True, True], [True, False], [True, True]])
    (hist,) = ref.node_histograms(shards, [(leaf, g, h, member)], 5)
    x = np.concatenate(shards, axis=1)
    want = np.zeros((3, 5, 2, 2))
    for r in range(80):
        for s in range(2):
            if member[leaf[r], s]:
                for c in range(3):
                    want[c, x[c, r], s] += (g[r], h[r])
    assert np.allclose(hist, want, atol=1e-5)
