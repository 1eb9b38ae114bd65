"""The harness has room for a token cell: a family that makes its own
token samples, added as files and entries alone, runs end to end with
its reference streaming two devices at a time and is correct under the
paper cell's limits, and a step that trains each device on half of its
batch is not."""
import json
import time

import numpy as np
import pytest

from chipbench import harness, traffic
from chipbench.conftest import ROOT
from chipbench.test_chipbench_faults import _broken_step, _half_batch

SEED = 2 ** 33 + 7          # a seed wider than 32 bits


def _run(cell):
    return harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                            require_tpu=False)


def test_a_token_cell_runs_and_is_correct(tiny_token_cell, streamed):
    streamed(tiny_token_cell, 2)
    res = _run(tiny_token_cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert res["compiles_in_window"] == 0
    assert set(res["checks"]) == set(tiny_token_cell.limits)


def test_a_token_cell_on_half_batches_is_not_correct(tiny_token_cell,
                                                     streamed, monkeypatch):
    streamed(tiny_token_cell, 2)
    _broken_step(monkeypatch, _half_batch)
    res = _run(tiny_token_cell)
    assert not res["correct"], res["checks"]
    c = res["checks"]["range_gap.median"]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("population,cohort", [(30, 30), (50, 10)],
                         ids=["disjoint", "windows"])
def test_shards_from_a_sample_count_are_those_from_labels(population,
                                                          cohort):
    """A family with no class per sample partitions by the number of
    samples, on the same draws; a Dirichlet split needs the classes."""
    cfg = json.loads((ROOT / "chipbench" / "configs" /
                      "ltfl-resnet-paper.json").read_text())
    cfg["deployment"].update(population=population, cohort=cohort)
    y = traffic.labels(SEED, 20000, 10, 0)
    for a, b in zip(traffic.devices_and_shards(SEED, cfg, y),
                    traffic.devices_and_shards(SEED, cfg, len(y))):
        if isinstance(a, dict):
            assert a.keys() == b.keys()
            a, b = list(a.values()), list(b.values())
        np.testing.assert_array_equal(a, b)
    cfg["deployment"]["non_iid_alpha"] = 0.1
    with pytest.raises(ValueError, match="class label per sample"):
        traffic.devices_and_shards(SEED, cfg, len(y))
