"""The reference streams the cohort where its work does not fit: k
devices at a time it gives what the whole cohort at once gives, and its
control and a half batch still read as far from the program as the
limits need."""
import jax
import numpy as np
import pytest

from chipbench import calibrate, compare, reference
from chipbench.conftest import write_tiny_cell
from chipbench.spec import load_cell
from chipbench.test_chipbench_reference import SEED, follow

RTOL = 5e-6


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    root = tmp_path_factory.mktemp("whole")
    return follow(load_cell(write_tiny_cell(root, scheme="ltfl",
                                            use_kernels=False), root), SEED)


@pytest.mark.parametrize("fit,k", [(None, 6), (100, 6), (6, 6), (0, 1),
                                   (0.5, 1), (3, 3), (2.999, 2)])
def test_the_cohort_streams_as_many_devices_as_fit(monkeypatch, fit, k):
    """All U = 6 devices where the backend sets no limit (the CPU) or
    they fit, else as many as fit in the budget (here ``fit`` devices'
    work, at ``TREES_PER_DEVICE`` float32 trees a device), and never fewer
    than one."""
    params = {"w": np.zeros((10, 10), np.float32), "b": np.zeros(0)}
    one = reference.TREES_PER_DEVICE * 4 * 100
    monkeypatch.setattr(reference, "_memory_budget",
                        lambda: None if fit is None else int(fit * one))
    assert reference.devices_per_step(params, 6) == k


@pytest.mark.parametrize("k", [1, 2, 4, 5])
def test_a_streamed_cohort_gives_the_whole_cohort_numbers(tiny_cell, whole,
                                                         streamed, k):
    """k devices at a time (6 = 4 + 2 for k = 4, 5 + 1 for k = 5) against
    the cohort in one vmap, to float32 rounding in the order of
    reduction: a vmap of another width orders a convolution's sums
    otherwise, and each chunk adds its weighted gradients to the running
    Eq. 19 sum, so the sum over the devices runs in another order. That
    moves a device's reading by a few float32 ulps (6e-8 each) and the
    aggregate by about U of them, more where its terms cancel; read at
    most 6.4e-7 on the range statistic, 8.8e-7 on the weights after the 3
    rounds and 2.1e-6 on the first aggregate's norm of a leaf. The limit
    is 5e-6 relative; a device left out of the sum, or counted twice,
    moves the aggregate by about a sixth."""
    cell = tiny_cell(scheme="ltfl", use_kernels=False)
    streamed(cell, k)
    got = follow(cell, SEED)
    np.testing.assert_array_equal(got["cohort"], whole["cohort"])
    np.testing.assert_allclose(got["loss"], whole["loss"], rtol=RTOL)
    np.testing.assert_allclose(got["range_sq"], whole["range_sq"],
                               rtol=RTOL)
    np.testing.assert_allclose(
        jax.tree_util.tree_leaves(got["grad_norm0"]),
        jax.tree_util.tree_leaves(whole["grad_norm0"]), rtol=RTOL)
    for a, b in zip(jax.tree_util.tree_leaves(got["params"]),
                    jax.tree_util.tree_leaves(whole["params"])):
        assert np.linalg.norm(a - b) <= RTOL * np.linalg.norm(b)


def test_streamed_one_device_at_a_time_the_control_and_a_half_batch_fail(
        tiny_cell, streamed):
    """As ``test_the_control_and_a_half_batch_fail``, with the reference
    and its two variants streaming one device at a time."""
    cell = tiny_cell(scheme="ltfl", partial=True)
    streamed(cell, 1)
    r = calibrate.readings(cell, SEED, ("program", "control", "half_batch"))
    assert r["cohorts_agree"]
    assert compare.passed(compare.checks(r["program"], cell.limits))
    for variant, number, times in (("control", "update_gap", 3),
                                   ("half_batch", "range_gap.median", 10)):
        assert r[variant][number] >= times * r["program"][number], \
            (variant, r[variant], r["program"])
    assert not compare.passed(compare.checks(r["half_batch"], cell.limits))


def test_the_share_has_the_sized_leaves():
    """``calibrate --masks`` times one chip's share of DeepSeek-V2-Lite: 53
    leaves of two or more dimensions, 535,035,904 entries."""
    shapes = calibrate.share_shapes()
    assert len(shapes) == 53
    assert sum(int(np.prod(s)) for s in shapes) == 535_035_904


def test_the_mask_timing_runs_at_k_1_and_2(monkeypatch):
    """``calibrate --masks`` on small leaves: set-up, then each round's
    sort, masks and round times at k = 1 and 2, then the peaks (none on
    the CPU)."""
    monkeypatch.setattr(calibrate, "share_shapes",
                        lambda: [(6, 5), (2, 3, 4), (40, 3)])
    lines = list(calibrate.mask_rounds(7, rounds=2, u=5))
    assert lines[0]["entries"] == 30 + 24 + 120
    assert [(x["k"], x["round"]) for x in lines[1:-1]] == \
        [(1, 0), (1, 1), (2, 0), (2, 1)]
    assert all(0 < x["sort_s"] < x["round_s"] and
               0 < x["masks_s"] < x["round_s"] for x in lines[1:-1])
    assert set(lines[-1]["peak_bytes"]) == {1, 2}
