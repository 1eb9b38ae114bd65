"""Pre-activation ResNet for the paper's CIFAR-10 experiments (Section 6).

"Our designed Residual neural network begins with an initial convolutional
layer that uses 64 3x3 kernels ... followed by four groups of residual
blocks ... global average pooling reducing the feature map to 1x1x512."

GroupNorm instead of BatchNorm: federated clients must not share batch
statistics, and per-client batches are small — standard FL practice.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.ltfl_paper import ResNetConfig
from repro.models.common import (
    ParamSpec,
    abstract_params,
    cross_entropy_loss,
    init_params,
)

PyTree = Any
GN_GROUPS = 8


def _conv_spec(k, cin, cout):
    return ParamSpec((k, k, cin, cout), (None, None, None, None), "normal",
                     scale=1.4, dtype=jnp.float32)


def _gn_spec(c):
    return {
        "gamma": ParamSpec((c,), (None,), "ones", dtype=jnp.float32),
        "beta": ParamSpec((c,), (None,), "zeros", dtype=jnp.float32),
    }


def group_norm(x: jax.Array, gamma, beta, groups=GN_GROUPS, eps=1e-5):
    """GroupNorm of (B, H, W, C) over ``min(groups, C)`` channel groups,
    with the two-pass variance of ``jnp.var``.

    Channels stay the minor dimension from input to output: sums are taken
    per channel over H and W, and only those (B, C) sums are grouped. A
    reshape of the activation to (..., g, C // g) would split the TPU's
    128-wide lane dimension whenever C // g < 128, and cost a relayout of
    the whole tensor each way, in the forward pass and in its gradient.
    """
    B, H, W, C = x.shape
    g = min(groups, C)
    n = H * W * (C // g)

    def group_total(s):                      # (B, C) -> (B, C)
        t = s.reshape(B, g, C // g).sum(-1)
        return jnp.repeat(t, C // g, axis=-1)

    mu = group_total(jnp.sum(x, axis=(1, 2))) / n
    d = x - mu[:, None, None, :]
    var = group_total(jnp.sum(d * d, axis=(1, 2))) / n
    scale = jax.lax.rsqrt(var + eps) * gamma
    return d * scale[:, None, None, :] + beta


def conv2d(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


class ResNet:
    def __init__(self, cfg: ResNetConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ #
    def param_specs(self) -> Dict:
        cfg = self.cfg
        specs: Dict[str, Any] = {
            "stem": _conv_spec(3, cfg.in_channels, cfg.stem_channels),
        }
        cin = cfg.stem_channels
        groups = []
        for gi, (cout, n_blocks) in enumerate(
                zip(cfg.group_channels, cfg.blocks_per_group)):
            blocks = []
            for bi in range(n_blocks):
                stride_in = cin if bi == 0 else cout
                block = {
                    "gn1": _gn_spec(stride_in),
                    "conv1": _conv_spec(3, stride_in, cout),
                    "gn2": _gn_spec(cout),
                    "conv2": _conv_spec(3, cout, cout),
                }
                if stride_in != cout:
                    block["proj"] = _conv_spec(1, stride_in, cout)
                blocks.append(block)
            groups.append(blocks)
            cin = cout
        specs["groups"] = groups
        specs["head_gn"] = _gn_spec(cin)
        specs["head_w"] = ParamSpec((cin, cfg.num_classes), (None, None),
                                    "normal", dtype=jnp.float32)
        specs["head_b"] = ParamSpec((cfg.num_classes,), (None,), "zeros",
                                    dtype=jnp.float32)
        return specs

    def init(self, key):
        return init_params(key, self.param_specs())

    def abstract_params(self):
        return abstract_params(self.param_specs())

    # ------------------------------------------------------------------ #
    def forward(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """batch: {'images': (B, H, W, C) f32} -> (logits (B, classes), 0)."""
        x = batch["images"].astype(jnp.float32)
        x = conv2d(x, params["stem"])
        for gi, blocks in enumerate(params["groups"]):
            for bi, bp in enumerate(blocks):
                stride = 2 if (gi > 0 and bi == 0) else 1
                h = jax.nn.relu(group_norm(x, bp["gn1"]["gamma"],
                                           bp["gn1"]["beta"]))
                shortcut = x
                if "proj" in bp:
                    shortcut = conv2d(h, bp["proj"], stride=stride)
                elif stride != 1:
                    shortcut = x[:, ::stride, ::stride, :]
                h = conv2d(h, bp["conv1"], stride=stride)
                h = jax.nn.relu(group_norm(h, bp["gn2"]["gamma"],
                                           bp["gn2"]["beta"]))
                h = conv2d(h, bp["conv2"])
                x = shortcut + h
        x = jax.nn.relu(group_norm(x, params["head_gn"]["gamma"],
                                   params["head_gn"]["beta"]))
        x = jnp.mean(x, axis=(1, 2))                   # global average pool
        logits = x @ params["head_w"] + params["head_b"]
        return logits, jnp.zeros((), jnp.float32)

    def loss(self, params, batch) -> jax.Array:
        logits, _ = self.forward(params, batch)
        return cross_entropy_loss(logits, batch["labels"])

    def accuracy(self, params, batch) -> jax.Array:
        logits, _ = self.forward(params, batch)
        return jnp.mean((jnp.argmax(logits, -1) == batch["labels"])
                        .astype(jnp.float32))
