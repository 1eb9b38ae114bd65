"""The plain reference: its outputs on the tiny ResNet cell are bit for bit
those of the golden file, and its pruning ranks and masks are those of a
stable host sort.

``testdata/reference-golden.json`` holds what the reference gave when it
ranked each leaf by a stable host ``np.argsort`` and held the whole
cohort in one vmap, under the jax and jaxlib of its ``versions``. XLA's
CPU backend splits a reduction by the number of cores it may use, so the
golden outputs are computed in a child process held to ``CORES`` cores;
a host with fewer skips the comparison. Another jax or jaxlib may round
otherwise, and the test says so before it compares. Regenerate the file
only where a change is meant to move the reference's numbers, or with
another jax or jaxlib:

    PYTHONPATH=src JAX_PLATFORMS=cpu python3 -m \
        chipbench.test_chipbench_reference --write
"""
import hashlib
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference

SEED = 2 ** 33 + 7          # a seed wider than 32 bits
CORES = 4
ROOT = Path(__file__).resolve().parents[1]
GOLDEN = ROOT / "chipbench" / "testdata" / "reference-golden.json"
CASES = {"ltfl-jnp-path": dict(scheme="ltfl", use_kernels=False),
         "fedsgd-partial-dirichlet": dict(scheme="fedsgd", partial=True)}
VARIANTS = {"reference": {}, "control": {"dtype": jnp.bfloat16},
            "half_batch": {"half_batch": True}}


def decisions(cfg, traffic_cfg, seed):
    """A round log of Algorithm 1's decisions drawn from the seed: every
    device's rho in [0, rho_max], rounds that hold 0 and rho_max, whole
    bit-widths in [1, delta_max] and packet error rates under 0.3."""
    u, lt = cfg["deployment"]["cohort"], cfg["ltfl"]
    r = traffic_cfg["rounds_per_call"]
    rng = np.random.default_rng([seed, 16])
    rho = rng.uniform(0.0, lt["rho_max"], (r, u))
    rho[0, 0], rho[-1, -1] = 0.0, lt["rho_max"]
    return {"rho": rho,
            "delta": rng.integers(1, lt["delta_max"] + 1, (r, u))
            .astype(np.float64),
            "pers": rng.uniform(0.0, 0.3, (r, u))}


def follow(cell, seed, **kw):
    return reference.follow(cell.config, cell.traffic, seed,
                            decisions(cell.config, cell.traffic, seed), **kw)


def _digest(tree) -> str:
    h = hashlib.sha256()
    for x in jax.tree_util.tree_leaves(tree):
        x = np.asarray(x)
        h.update(f"{x.dtype}{x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()


def _hex(x) -> list:
    return [float(v).hex() for v in np.asarray(x, np.float64).reshape(-1)]


def outputs(ref) -> dict:
    """``reference.follow``'s outputs as exact text: floats in hex, the
    weights after the call and the channel as digests."""
    return {"loss": _hex(ref["loss"]), "range_sq": _hex(ref["range_sq"]),
            "cohort": np.asarray(ref["cohort"]).reshape(-1).tolist(),
            "grad_norm0": _hex(jax.tree_util.tree_leaves(ref["grad_norm0"])),
            "params": _digest(ref["params"]),
            "channel": _digest(ref["channel"])}


def _all_outputs(tmp: Path) -> dict:
    from chipbench.conftest import write_tiny_cell
    from chipbench.spec import load_cell
    out = {}
    for case, kw in CASES.items():
        root = tmp / case
        cell = load_cell(write_tiny_cell(root, **kw), root)
        out[case] = {v: outputs(follow(cell, SEED, **vkw))
                     for v, vkw in VARIANTS.items()}
    return out


def _versions() -> dict:
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__}


def _pinned_outputs() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.test_chipbench_reference"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.fixture(scope="module")
def pinned():
    if len(os.sched_getaffinity(0)) < CORES:
        pytest.skip(f"the golden outputs were computed on {CORES} cores; "
                    f"this process may use "
                    f"{len(os.sched_getaffinity(0))}")
    return _pinned_outputs()


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("case", list(CASES))
def test_the_reference_is_bitwise_the_golden_one(pinned, case, variant):
    golden = json.loads(GOLDEN.read_text())
    assert golden["versions"] == _versions(), (
        f"the golden file was written under {golden['versions']}, this is "
        f"{_versions()}: another XLA may round otherwise")
    assert pinned[case][variant] == golden[case][variant]


def _host_ranks(w: np.ndarray) -> np.ndarray:
    """Each entry's position in a stable host argsort of |w|."""
    flat = np.abs(np.asarray(w, np.float32)).reshape(-1)
    rank = np.empty(flat.size, np.int64)
    rank[np.argsort(flat, kind="stable")] = np.arange(flat.size)
    return rank.reshape(w.shape)


def _host_masks(w: np.ndarray, rho: np.ndarray) -> np.ndarray:
    """(U, *shape) masks from a stable host argsort: an entry is kept
    where its rank is at least floor(rho * n), the cut taken in float32
    as the device takes it."""
    cuts = np.asarray(jnp.floor(jnp.clip(jnp.asarray(rho, jnp.float32),
                                         0.0, 1.0) * w.size)
                      .astype(jnp.int32))
    return np.stack([_host_ranks(w) >= c for c in cuts])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_device_ranks_and_masks_are_those_of_a_stable_host_sort(dtype):
    """Leaves of few distinct magnitudes (ties everywhere), planted zeros
    of both signs, and rho at 0, at rho_max = 0.5, at 1 and between: the
    ranks the round's step takes, and the masks a streamed chunk takes."""
    rng = np.random.default_rng(3)
    leaves = []
    for shape in [(7, 9), (3, 4, 5), (64, 33)]:
        w = rng.integers(-6, 7, shape).astype(np.float32) / 4
        w.reshape(-1)[rng.choice(w.size, w.size // 5, replace=False)] = -0.0
        leaves.append(jnp.asarray(w, dtype))
    rho = np.array([0.0, 0.5, 1.0, 0.1, 0.33, 0.49, 0.0625, 0.9],
                   np.float32)
    bounds = [reference._boundaries(w, jnp.asarray(rho)) for w in leaves]
    masks = reference._masks(leaves, [b[0] for b in bounds],
                             [b[1] for b in bounds])
    for w, mk in zip(leaves, masks):
        host = np.asarray(w, np.float32)
        np.testing.assert_array_equal(np.asarray(reference._ranks(w)),
                                      _host_ranks(host))
        np.testing.assert_array_equal(np.asarray(mk), _host_masks(host, rho))


if __name__ == "__main__":
    os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:CORES])
    with tempfile.TemporaryDirectory() as d:
        result = _all_outputs(Path(d))
    if sys.argv[1:] == ["--write"]:
        GOLDEN.write_text(json.dumps({"versions": _versions(), **result},
                                     indent=1) + "\n")
    else:
        print(json.dumps(result))
