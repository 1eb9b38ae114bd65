"""Sharding rule table: divisibility fallback + mesh-axis dedupe.
Uses AbstractMesh — no devices required."""
import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.launch.sharding import base_rules, make_pspec

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_divisible_dims_shard():
    rules = base_rules(MESH)
    spec = make_pspec((4096, 14336), ("embed", "d_ff"), rules, MESH)
    assert spec == P(None, "model")


def test_indivisible_head_falls_back_to_head_dim():
    rules = base_rules(MESH)
    # nemotron kv cache: 8 kv heads can't split 16 ways; head_dim takes it
    spec = make_pspec((96, 128, 32768, 8, 192),
                      ("layers", "batch", "seq", "kv_heads", "head_dim"),
                      rules, MESH)
    assert spec == P(None, "data", None, None, "model")


def test_divisible_kv_keeps_heads_and_dedupes_head_dim():
    rules = base_rules(MESH)
    spec = make_pspec((36, 128, 32768, 16, 128),
                      ("layers", "batch", "seq", "kv_heads", "head_dim"),
                      rules, MESH)
    assert spec == P(None, "data", None, "model", None)


def test_client_axis_consumes_data():
    rules = base_rules(MESH, client_axes=("data",))
    spec = make_pspec((16, 16, 4096), ("client", "batch", "seq"),
                      rules, MESH)
    # batch rule wants data too, but client already took it
    assert spec == P("data", None, None)


def test_multi_pod_batch():
    rules = base_rules(MESH3)
    spec = make_pspec((256, 4096), ("batch", "seq"), rules, MESH3)
    assert spec == P(("pod", "data"), None)


def test_fsdp_shards_embed_over_data():
    rules = base_rules(MESH, fsdp=True)
    spec = make_pspec((18432, 73728), ("embed", "d_ff"), rules, MESH)
    assert spec == P("data", "model")


def test_batch_of_one_replicates():
    rules = base_rules(MESH)
    spec = make_pspec((1,), ("batch",), rules, MESH)
    assert spec == P(None)
