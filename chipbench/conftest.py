"""Fixtures of the benchmark's CPU tests: a tiny cell written to a
temporary checkout, the way a later change adds a cell (new files and
new entries only)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def write_tiny_cell(root: Path, *, scheme: str = "ltfl",
                    partial: bool = False, use_kernels: bool = True,
                    limits_from: str = "paper-ltfl-u30",
                    e_max: float = None) -> str:
    """A 16x16-pixel, width-8 ResNet cell of 3-round calls under
    ``root`` (``e_max`` in place of Table 2's energy budget); returns the
    cell's name."""
    here = ROOT / "chipbench"
    cfg = json.loads((here / "configs" / "ltfl-resnet-paper.json")
                     .read_text())
    cfg["model"].update(image_size=16, stem_channels=8,
                        group_channels=[8, 16, 32, 64])
    cfg["deployment"].update(batch_size=4, train_samples=400,
                             test_samples=256)
    cfg["ltfl"].update(samples_min=20, samples_max=30)
    if e_max is not None:
        cfg["ltfl"]["e_max"] = e_max
    if partial:
        cfg["deployment"].update(population=12, cohort=4,
                                 non_iid_alpha=0.5)
    else:
        cfg["deployment"].update(population=6, cohort=6)
    traffic = json.loads((here / "traffic" / (
        "fedsgd.json" if scheme == "fedsgd" else "ltfl-recontrol1.json"))
        .read_text())
    traffic.update(rounds_per_call=3, eval_every=3, use_kernels=use_kernels)
    return _write_cell(root, "tiny", cfg, traffic, limits_from)


def _write_cell(root: Path, name: str, cfg: dict, traffic: dict,
                limits_from: str) -> str:
    """The configuration, traffic and limits files of the cell
    ``<name>-cell``, and a BENCHMARK.json that holds it alone."""
    here = ROOT / "chipbench"
    for d in ("configs", "traffic", "limits"):
        (root / "chipbench" / d).mkdir(parents=True, exist_ok=True)
    cell = f"{name}-cell"
    (root / "chipbench" / "configs" / f"{name}.json").write_text(
        json.dumps(cfg))
    (root / "chipbench" / "traffic" / f"{name}.json").write_text(
        json.dumps(traffic))
    (root / "chipbench" / "limits" / f"{cell}.json").write_text(
        (here / "limits" / f"{limits_from}.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": name, "source": "a test",
                         "file": f"chipbench/configs/{name}.json",
                         "reduced": [], "why": "a test"}]
    bench["workloads"] = [{"name": cell, "config": name,
                           "traffic": name, "chips": 1, "why": "a test"}]
    for m in bench["per_layer"]:
        m["workloads"] = [cell]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


def write_tiny_token_cell(root: Path) -> str:
    """A token cell added as a later change adds one, by new files and
    entries only: the family ``tiny_lm`` (``testdata/tiny_lm.py``: its own
    seeded token samples, a toy next-token program model and its plain
    reference) under ``root/chipbench/families/``, 5 devices of batch 8,
    3-round LTFL calls and the paper cell's limits; returns the cell's
    name."""
    here = ROOT / "chipbench"
    (root / "chipbench" / "families").mkdir(parents=True, exist_ok=True)
    (root / "chipbench" / "families" / "tiny_lm.py").write_text(
        (here / "testdata" / "tiny_lm.py").read_text())
    cfg = json.loads((here / "configs" / "ltfl-resnet-paper.json")
                     .read_text())
    cfg.update(name="tiny-lm", family="tiny_lm",
               model={"vocab_size": 64, "seq_len": 16, "width": 32},
               parameters=2 * 64 * 32 + 64, reduced={}, assumed=[])
    cfg["deployment"].update(population=5, cohort=5, batch_size=8,
                             train_samples=400, test_samples=256)
    cfg["ltfl"].update(samples_min=20, samples_max=30)
    traffic = json.loads((here / "traffic" / "ltfl-recontrol1.json")
                         .read_text())
    traffic.update(rounds_per_call=3, eval_every=3, use_kernels=False)
    return _write_cell(root, "tiny-lm", cfg, traffic, "paper-ltfl-u30")


@pytest.fixture
def tiny_cell(tmp_path):
    """Factory: ``tiny_cell(**kw)`` -> the loaded ``Cell``."""
    from chipbench.spec import load_cell

    def make(**kw):
        return load_cell(write_tiny_cell(tmp_path, **kw), tmp_path)
    return make


@pytest.fixture
def tiny_token_cell(tmp_path, monkeypatch):
    """The loaded token ``Cell``. The harness imports a family from the
    ``chipbench.families`` package, so the temporary checkout's
    ``families/`` joins the package's path, as if the family's file were
    in this one."""
    import chipbench.families
    from chipbench.spec import load_cell
    cell = write_tiny_token_cell(tmp_path)
    monkeypatch.setattr(chipbench.families, "__path__", [
        str(tmp_path / "chipbench" / "families"),
        *chipbench.families.__path__])
    monkeypatch.delitem(sys.modules, "chipbench.families.tiny_lm",
                        raising=False)
    yield load_cell(cell, tmp_path)
    sys.modules.pop("chipbench.families.tiny_lm", None)


@pytest.fixture
def streamed(monkeypatch):
    """``streamed(cell, k)`` has the reference of ``cell`` run k devices
    at a time: the device's memory budget is set to what k devices' work
    takes."""
    import jax

    from chipbench import reference
    from chipbench.spec import family

    def set_k(cell, k):
        fam, m = family(cell.config["family"]), cell.config["model"]
        tree = 4 * sum(x.size for x in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: fam.init_params(m, jax.random.PRNGKey(0)))))
        monkeypatch.setattr(reference, "_memory_budget",
                            lambda: k * reference.TREES_PER_DEVICE * tree)
    return set_k
