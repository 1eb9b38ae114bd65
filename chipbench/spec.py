"""Finds each piece of a cell by its name.

``BENCHMARK.json`` (at the root of the checkout) names the cell's
configuration and traffic. The pieces live in files of their own, so a
cell, a configuration or a per-layer metric is added with new files and
new entries and no edit:

* a configuration: the JSON file ``BENCHMARK.json`` gives for it; its
  ``family`` names the module ``families/<family>.py`` that makes its
  weights, holds its plain reference and counts its FLOPs, and its
  deployment's ``sampler`` the module ``samplers/<sampler>.py`` that
  draws the reference's cohorts;
* a traffic mix: ``traffic/<traffic>.json``, whose ``engine`` names
  ``engines/<engine>.py`` (builds and drives the program's runner, and
  gives the reference its key layout and aggregation) and whose
  ``scheme`` names ``schemes/<scheme>.py`` (the program's scheme, and
  the reference's compression, uplink bits and control checks);
* a cell's limits on the comparison that decides ``correct``:
  ``limits/<cell>.json``;
* a per-layer metric: ``metrics/<metric>.py``, whose ``read(ctx)``
  returns the value, or None where the run has nothing to read;
* the chip's peaks: ``peaks.json``, keyed by ``device_kind``.

A family defines:

* ``init_params(m, key)``: the weights of the model sizes ``m``, as the
  program reads them, in one jitted call; ``num_params(m)``;
* ``INPUT_KEY``: the key of the batch whose rows the reference hands to
  ``loss`` beside those of ``"labels"``; ``PARTITION_KEY``: the key of
  one class label per sample that the partition reads, or None to
  partition by the number of samples (``non_iid_alpha`` 0);
* ``loss(params, inputs, labels, m, precision)``: the plain reference;
* ``flops_per_sample(m)``: model FLOPs of one sample's forward and
  backward pass; ``XLA_FLOPS_RATIO``: the range that count keeps to
  against XLA's CPU count of the same loss and gradient;
* ``program_model(m)``: the system under test;
* optionally ``dataset(seed, cfg) -> (train, test)``, dicts of arrays
  keyed as the program model's batch; without it the samples are
  ``traffic.dataset``'s images and class labels;
* what a metric of its cells reads, such as ``quant_entries(m)``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)


def _read(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its
    configuration, traffic, limits and the metrics it reports."""
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(root / configs[w["config"]]["file"])
    here = root / "chipbench"
    traffic = _read(here / "traffic" / f"{w['traffic']}.json")
    limits = _read(here / "limits" / f"{workload}.json")

    def reports(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[x for x in bench["end_to_end"] if reports(x)],
                per_layer=[x for x in bench["per_layer"] if reports(x)])


def module(kind: str, name: str):
    """The module ``chipbench/<kind>/<name>.py``."""
    return importlib.import_module(f"chipbench.{kind}.{name}")


def family(name: str):
    """The module ``chipbench/families/<name>.py``."""
    return module("families", name)


def metric_reader(name: str, root: Path = ROOT):
    """``read`` of ``chipbench/metrics/<name>.py`` (loaded by path, so a
    name may hold dots)."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's peaks from ``peaks.json``; an unknown chip is an
    error."""
    table = _read(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json; have "
                       f"{sorted(table['devices'])}")
    return table["devices"][device_kind]
