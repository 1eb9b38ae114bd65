"""HLO analysis (scan-aware flops/bytes/collectives) + a tiny-mesh dry-run
smoke via subprocess (jax device count is locked at first init, so the
multi-device cases need fresh interpreters)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.launch.hlo_analysis import (
    _shape_bytes,
    _wire_factor,
    collective_bytes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ,
           PYTHONPATH=os.path.join(REPO, "src"),
           REPRO_XLA_FLAGS="--xla_force_host_platform_device_count=8")


def _run(code: str, timeout=420) -> str:
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=ENV, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_shape_bytes():
    assert _shape_bytes("f32[2,3]") == 24
    assert _shape_bytes("bf16[10]") == 20
    assert _shape_bytes("(s32[4], f32[2,2])") == 32
    assert _shape_bytes("pred[]") == 1


def test_wire_factors():
    assert _wire_factor("all-reduce", 4) == pytest.approx(1.5)
    assert _wire_factor("all-gather", 4) == 3.0
    assert _wire_factor("reduce-scatter", 4) == pytest.approx(0.75)
    assert _wire_factor("all-reduce", 1) == 0.0


def test_scan_flops_exact_subprocess():
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.launch.hlo_analysis import analyze_hlo
        def scanmodel(x, ws):
            def body(c, w):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return y.sum()
        c = jax.jit(scanmodel).lower(
            jax.ShapeDtypeStruct((128, 256), jnp.float32),
            jax.ShapeDtypeStruct((16, 256, 256), jnp.float32)).compile()
        r = analyze_hlo(c.as_text())
        print("FLOPS", r["flops"])
    """)
    flops = float(out.split("FLOPS")[1].strip())
    assert flops == 16 * 2 * 128 * 256 * 256


def test_collective_bytes_on_sharded_matmul():
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_analysis import collective_bytes
        mesh = jax.make_mesh((8,), ("model",))
        sh = NamedSharding(mesh, P("model", None))
        # contraction over a sharded dim => all-reduce of the (128,128) out
        f = jax.jit(lambda a, b: a.T @ b, in_shardings=(sh, sh),
                    out_shardings=NamedSharding(mesh, P()))
        c = f.lower(jax.ShapeDtypeStruct((1024, 128), jnp.float32),
                    jax.ShapeDtypeStruct((1024, 128), jnp.float32)).compile()
        r = collective_bytes(c.as_text())
        print("AR", r["all-reduce"], "WIRE", r["wire_total"])
    """)
    ar = float(out.split("AR")[1].split("WIRE")[0])
    wire = float(out.split("WIRE")[1])
    assert ar == 128 * 128 * 4          # one all-reduce of the f32 output
    assert wire == pytest.approx(ar * 2 * 7 / 8)


@pytest.mark.parametrize("arch,shape", [
    ("granite-8b", "decode_32k"),
    ("whisper-medium", "prefill_32k"),
    ("rwkv6-7b", "long_500k"),
])
def test_dryrun_tiny_mesh(arch, shape, tmp_path):
    """Full-size configs lower + compile on the CI mesh (deliverable (e)
    machinery; the production 16x16 / 2x16x16 runs live in artifacts/)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--test-mesh", "--out", str(tmp_path)],
        env=ENV, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "dry-run complete" in out.stdout


def test_dryrun_scanned_train_variant(tmp_path):
    """variant {"scan": R} AOT-lowers R federated rounds as one scanned
    segment (the scan engine's datacenter shape) and roughly R-scales the
    roofline FLOPs vs the single-round step."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "granite-8b", "--shape", "train_4k", "--test-mesh",
         "--variant", '{"scan": 2}', "--out", str(tmp_path)],
        env=ENV, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "dry-run complete" in out.stdout


def test_dryrun_skip_documented(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "qwen1.5-32b", "--shape", "long_500k", "--test-mesh", "--out",
         str(tmp_path)],
        env=ENV, capture_output=True, text=True, timeout=180)
    assert out.returncode == 0
    assert "SKIP" in out.stdout
