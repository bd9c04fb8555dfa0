"""The numbers that decide ``correct``, on hand-made inputs."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare


def test_worst_slice_gap_uses_the_larger_of_slice_and_median():
    want = np.array([1.0, 2.0, 4.0, 1e-6])
    got = np.array([1.1, 2.0, 4.0, 2e-6])
    # the tiny slice is measured against the median (1.5), not itself
    assert compare.worst_slice_gap(got, want) == pytest.approx(0.1 / 1.5)
    assert compare.worst_slice_gap(want, want) == 0.0


def test_moving_slices_rule():
    g = np.array([1.0, 2.0, 3.0, 1e-4, 2.9e-3])
    keep = compare.moving_slices(g)
    assert keep.tolist() == [True, True, True, False, True]


def test_slice_norms_split_stacked_layers():
    tree = {"layers": {"w": jnp.ones((3, 2, 2))},
            "final": jnp.full((4,), 2.0)}
    n = compare.slice_norms(tree)
    assert n.tolist() == pytest.approx([4.0, 2.0, 2.0, 2.0])
    d = compare.diff_norms(tree, {"layers": {"w": jnp.zeros((3, 2, 2))},
                                  "final": jnp.full((4,), 2.0)})
    assert d.tolist() == pytest.approx([0.0, 2.0, 2.0, 2.0])


def test_train_numbers():
    ref = {"losses": [10.0, 9.0], "grad_norms": np.array([1.0, 2.0]),
           "update_norms": np.array([0.5, 0.5])}
    prog = {"losses": [10.001, 9.01], "grad_norms": np.array([1.0, 2.2]),
            "update_norms": np.array([0.5, 0.0])}
    out = compare.train_numbers(prog, ref)
    assert out["loss_gap"] == pytest.approx(0.01)
    assert out["grad_norm_gap"] == pytest.approx(0.1)
    assert out["update_norm_gap"] == pytest.approx(1.0)

