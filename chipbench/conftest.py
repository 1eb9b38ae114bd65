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
    for d in ("configs", "traffic", "limits"):
        (root / "chipbench" / d).mkdir(parents=True, exist_ok=True)
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
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(cfg))
    (root / "chipbench" / "traffic" / "tiny.json").write_text(
        json.dumps(traffic))
    (root / "chipbench" / "limits" / "tiny-cell.json").write_text(
        (here / "limits" / f"{limits_from}.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "a test",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "a test"}]
    bench["workloads"] = [{"name": "tiny-cell", "config": "tiny",
                           "traffic": "tiny", "chips": 1, "why": "a test"}]
    for m in bench["per_layer"]:
        m["workloads"] = ["tiny-cell"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tiny-cell"


@pytest.fixture
def tiny_cell(tmp_path):
    """Factory: ``tiny_cell(**kw)`` -> the loaded ``Cell``."""
    from chipbench.spec import load_cell

    def make(**kw):
        return load_cell(write_tiny_cell(tmp_path, **kw), tmp_path)
    return make
