"""DeepSeek-V2-Lite's share (``families/deepseek_v2.py``): the program's
DecoderLM against the family's plain reference at a small width, the
configuration against the repository's published config, and a small
cell of the family end to end with one device at a time in the
program."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.conftest import ROOT, _write_cell
from chipbench.families import deepseek_v2 as fam
from chipbench.spec import load_cell

CONFIG = ROOT / "chipbench" / "configs" / "dsv2-lite-ep8.json"
# the published widths cut small, with everything else as configured:
# 2 of 8 routed experts (ids 2-3), top-3, YaRN as published
SMALL = dict(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
             kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
             v_head_dim=8, n_routed_experts=8, experts_held=2,
             first_expert=2, num_experts_per_tok=3, moe_intermediate_size=32,
             intermediate_size=96, vocab_size=128, seq_len=32,
             num_hidden_layers=3)


def _config():
    return json.loads(CONFIG.read_text())


def _small():
    cfg = _config()
    cfg["model"].update(SMALL)
    return cfg


def test_the_configuration_is_the_published_share():
    """The file's top-level keys are the published config's, two of them
    cut as ``reduced`` says; ``model`` holds the same numbers and the
    share; the program builds exactly ``ep_share`` of the repository's
    DeepSeek-V2-Lite from it; 535,060,992 entries."""
    from repro.configs.deepseek_v2_lite_16b import CONFIG as PUBLISHED
    from repro.configs.deepseek_v2_lite_16b import ep_share, from_hf
    cfg = _config()
    m = cfg["model"]
    top = {k: cfg[k] for k in m if k in cfg}
    assert top == {k: m[k] for k in top}
    assert set(cfg["reduced"]) == {"num_hidden_layers", "vocab_size",
                                   "experts_held"}
    published = dict(m, num_hidden_layers=27, vocab_size=102400,
                     experts_held=0)
    assert from_hf(published) == PUBLISHED
    assert fam.program_model(m).cfg == ep_share().replace(
        name=PUBLISHED.name)
    assert fam.num_params(m) == cfg["parameters"] == 535_060_992
    # 725.6 MFLOP a token forward, the routed experts at 6 x 8 / 64
    assert fam.flops_per_sample(m) == 3 * 2 * 4096 * 362_807_296
    assert fam.expert_flops(m, 2) == 3 * 2 * 8192 * 4 * 0.75 * 3 * 2048 \
        * 1408


def test_program_loss_and_gradient_are_the_reference():
    """At HIGHEST precision on seeded weights and rows, DecoderLM's loss
    and gradient equal the family's reference: the loss to 1e-5 (float32
    sums over 64 positions and 128 classes in another order), each
    gradient leaf to 1e-4 of its largest entry (the same sums, and the
    grouped products against each expert run densely on every token)."""
    m = _small()["model"]
    params = fam.init_params(m, jax.random.PRNGKey(0))
    rows = jnp.asarray(fam._rows(5, 2, m, 0))
    model = fam.program_model(m)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(model.loss))(
            params, {"tokens": rows, "labels": rows})
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p: fam.loss(p, rows, rows, m)))(params)
    np.testing.assert_allclose(lp, lr, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=1e-4 * scale, rtol=0)


@pytest.fixture
def small_cell(tmp_path, monkeypatch):
    """The small cell of 8 silos, 2-round calls, one device at a time in
    the program (the reference's streaming has tests of its own)."""
    import repro.fed.rounds as rounds
    cfg = _small()
    cfg["deployment"].update(train_samples=64, test_samples=2)
    cfg["ltfl"].update(samples_min=6, samples_max=8)
    traffic = json.loads((ROOT / "chipbench" / "traffic" /
                          "ltfl-recontrol1-r2.json").read_text())
    monkeypatch.setattr(rounds, "clients_per_step", lambda params, n: 1)
    return load_cell(_write_cell(tmp_path, "dsv2", cfg, traffic,
                                 "dsv2-ep8-ltfl-u8"), tmp_path)


def test_a_small_share_cell_is_correct_streamed(small_cell):
    res = harness.run_cell(small_cell, 2 ** 33 + 7, 0.5, False,
                           time.perf_counter(), require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["compiles_in_window"] == 0
    assert set(res["checks"]) == set(small_cell.limits)
