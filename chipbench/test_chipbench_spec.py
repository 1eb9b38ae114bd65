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


def test_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    assert [m["name"] for m in BENCH["end_to_end"]] == ["round_s", "setup_s"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_every_piece_exists_and_every_metric_reports_its_move():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    here = ROOT / "chipbench"
    for w in cells.values():
        assert w["config"] in configs
        t = json.loads((here / "traffic" / f"{w['traffic']}.json")
                       .read_text())
        assert (here / "engines" / f"{t['engine']}.py").is_file()
        assert (here / "schemes" / f"{t['scheme']}.py").is_file()
        assert (here / "limits" / f"{w['name']}.json").is_file()
    for c in configs.values():
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert (here / "families" / f"{cfg['family']}.py").is_file()
        assert (here / "samplers" /
                f"{cfg['deployment']['sampler']}.py").is_file()
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert (here / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", list(cells))
        for cell in m["workloads"]:
            assert cell in cells and cell in moved


def test_configs_count_their_parameters():
    from chipbench.spec import family
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        fam = family(cfg["family"])
        assert fam.num_params(cfg["model"]) == cfg["parameters"]


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


@pytest.mark.parametrize("config", sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "chipbench" / "configs")
    .glob("*.json")))
def test_flops_beside_xla_cost_analysis(config):
    """The analytic count is the dense one; XLA's CPU count leaves out the
    zero border of 'SAME' convolutions (see ``flops_per_image``)."""
    import jax
    import jax.numpy as jnp

    from chipbench.spec import family
    cfg = json.loads((ROOT / config).read_text())
    fam, m = family(cfg["family"]), cfg["model"]
    params = jax.eval_shape(lambda: fam.init_params(m, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((1, m["image_size"], m["image_size"],
                              m["in_channels"]), jnp.float32)
    y = jax.ShapeDtypeStruct((1,), jnp.int32)
    step = jax.jit(jax.value_and_grad(lambda p, x, y: fam.loss(p, x, y, m)))
    cost = step.lower(params, x, y).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ratio = fam.flops_per_image(m) / cost["flops"]
    assert 1.08 < ratio < 1.16, ratio
