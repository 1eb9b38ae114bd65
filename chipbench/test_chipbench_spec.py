"""The benchmark's description holds to its contract, and the run refuses
to measure anything but a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _names_units_and_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    assert [m["name"] for m in bench["end_to_end"]] == ["round_s", "setup_s"]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_names_units_and_keys():
    _names_units_and_keys(BENCH)


def _every_piece_exists(bench, root):
    """Each piece is found in the checkout ``root`` or, where a later
    change adds no file of that kind, in this one."""
    def piece(*parts):
        return next((r / "chipbench" / Path(*parts) for r in (root, ROOT)
                     if (r / "chipbench" / Path(*parts)).is_file()),
                    root / "chipbench" / Path(*parts))

    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    for w in cells.values():
        assert w["config"] in configs
        t = json.loads(piece("traffic", f"{w['traffic']}.json").read_text())
        assert piece("engines", f"{t['engine']}.py").is_file()
        assert piece("schemes", f"{t['scheme']}.py").is_file()
        assert piece("limits", f"{w['name']}.json").is_file()
    for c in configs.values():
        cfg = json.loads((root / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert piece("families", f"{cfg['family']}.py").is_file()
        assert piece("samplers",
                     f"{cfg['deployment']['sampler']}.py").is_file()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert piece("metrics", f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", list(cells))
        for cell in m["workloads"]:
            assert cell in cells and cell in moved


def test_every_piece_exists_and_every_metric_reports_its_move():
    _every_piece_exists(BENCH, ROOT)


def _configs_count_their_parameters(bench, root):
    from chipbench.spec import family
    for c in bench["configs"]:
        cfg = json.loads((root / c["file"]).read_text())
        fam = family(cfg["family"])
        assert fam.num_params(cfg["model"]) == cfg["parameters"]


def test_configs_count_their_parameters():
    _configs_count_their_parameters(BENCH, ROOT)


def test_a_token_cell_added_by_files_passes_the_spec_checks(
        tiny_token_cell, tmp_path):
    """The checkout ``tmp_path``, with a token family, its configuration,
    traffic and limits added as files and entries, holds to what this one
    does."""
    root = tmp_path
    bench = json.loads((root / "BENCHMARK.json").read_text())
    _names_units_and_keys(bench)
    _every_piece_exists(bench, root)
    _configs_count_their_parameters(bench, root)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "paper-ltfl-u30",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


# a configuration a later change adds by files: ``write_tiny_token_cell``
TOKEN_CONFIG = "tiny-lm (a token family added by files)"


@pytest.mark.parametrize("config", sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "chipbench" / "configs")
    .glob("*.json")) + [TOKEN_CONFIG])
def test_flops_beside_xla_cost_analysis(config, request):
    """The analytic count is the dense one; XLA's CPU count of one
    sample's loss and gradient differs from it as the family states in
    ``XLA_FLOPS_RATIO`` (for a ResNet, XLA leaves out the zero border of
    'SAME' convolutions: see its ``flops_per_sample``). The sample is one
    of the family's own, in the shape and type its ``loss`` takes."""
    import jax

    from chipbench import traffic
    from chipbench.spec import family
    if config == TOKEN_CONFIG:
        cfg = request.getfixturevalue("tiny_token_cell").config
    else:
        cfg = json.loads((ROOT / config).read_text())
    fam, m = family(cfg["family"]), cfg["model"]
    params = jax.eval_shape(lambda: fam.init_params(m, jax.random.PRNGKey(0)))
    train, _ = traffic.samples(0, dict(cfg, deployment=dict(
        cfg["deployment"], train_samples=1, test_samples=1)))
    x, y = (jax.ShapeDtypeStruct(train[k].shape, train[k].dtype)
            for k in (fam.INPUT_KEY, "labels"))
    step = jax.jit(jax.value_and_grad(lambda p, x, y: fam.loss(p, x, y, m)))
    cost = step.lower(params, x, y).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ratio = fam.flops_per_sample(m) / cost["flops"]
    lo, hi = fam.XLA_FLOPS_RATIO
    assert lo < ratio < hi, ratio
