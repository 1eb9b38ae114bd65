"""A cell added as files alone runs end to end on the CPU, and the plain
reference agrees with the program's rounds while its control does not."""
import time

import pytest

from chipbench import calibrate, compare, harness

SEED = 2 ** 33 + 7          # a seed wider than 32 bits


@pytest.mark.parametrize("kw", [
    dict(scheme="ltfl", use_kernels=False),
    dict(scheme="fedsgd", partial=True),
], ids=["ltfl-jnp-path", "fedsgd-partial-dirichlet"])
def test_a_new_cell_runs_and_is_correct(tiny_cell, kw):
    cell = tiny_cell(**kw)
    res = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                           require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert res["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"round_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(cell.limits) <= set(compare.NAMES)


def test_the_control_and_a_half_batch_fail(tiny_cell):
    """In bfloat16 (one precision below the configuration's) and with half
    of each batch left out, the reference's own rounds read as far above
    the program's as a limit needs: the control at three times the
    program's weight change gap or more, half a batch at ten times its
    range gap or more, and past the cell's limit. (At this width bfloat16
    weights still take most of a step, so the control's gap stays under
    the limit set at the paper's width, where it reads about 1.)"""
    cell = tiny_cell(scheme="ltfl", partial=True)
    r = calibrate.readings(cell, SEED, ("program", "control", "half_batch"))
    assert r["cohorts_agree"]
    assert compare.passed(compare.checks(r["program"], cell.limits))
    for variant, number, times in (("control", "update_gap", 3),
                                   ("half_batch", "range_gap.median", 10)):
        assert r[variant][number] >= times * r["program"][number], \
            (variant, r[variant], r["program"])
    assert not compare.passed(compare.checks(r["half_batch"], cell.limits))


@pytest.mark.parametrize("population,cohort,alpha", [
    (30, 30, 0.0), (50, 10, 0.0), (50, 10, 0.1)],
    ids=["disjoint", "windows", "dirichlet"])
def test_shards_follow_the_program_draws(population, cohort, alpha):
    """The reference's copy of the registry and partition draws gives the
    program's own shard table, seed for seed."""
    import json

    import numpy as np

    from chipbench import traffic
    from chipbench.conftest import ROOT
    from repro.configs.base import WirelessConfig
    from repro.data import ArrayDataset, ClientBatcher, dirichlet_partition, \
        iid_partition, population_partition
    from repro.fed.population import Population
    cfg = json.loads((ROOT / "chipbench" / "configs" /
                      "ltfl-resnet-paper.json").read_text())
    cfg["deployment"].update(population=population, cohort=cohort,
                             non_iid_alpha=alpha)
    y = traffic.labels(SEED, 20000, 10, 0)
    sizes, table, registry = traffic.devices_and_shards(SEED, cfg, y)
    rng = np.random.default_rng(SEED)
    lt = cfg["ltfl"]
    pop = Population.sample(WirelessConfig(**cfg["wireless"]), population,
                            lt["samples_min"], lt["samples_max"], rng)
    n = pop.channel.num_samples
    if alpha > 0:
        parts = dirichlet_partition(y, n, alpha, rng)
    elif cohort == population:
        parts = iid_partition(len(y), n, rng)
    else:
        parts = population_partition(len(y), n, rng)
    batcher = ClientBatcher(ArrayDataset({"labels": y}), parts)
    np.testing.assert_array_equal(sizes, n)
    np.testing.assert_array_equal(registry["distance"], pop.channel.distance)
    np.testing.assert_array_equal(registry["cpu"], pop.channel.cpu_hz)
    np.testing.assert_array_equal(table, batcher.padded_parts())
