"""Pre-activation ResNet with GroupNorm: weights from the seed, the plain
float32 reference of its loss, and its model FLOPs.

The parameter tree has the layout the program's ``repro.models.resnet``
reads (``stem``, ``groups`` as lists of block dicts, ``head_gn``,
``head_w``, ``head_b``), so the benchmark makes the weights and hands the
same tree to the program and to the reference. Nothing here imports the
program except ``program_model``, which builds the system under test.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

PyTree = Any

# the batch key the reference hands to ``loss`` beside "labels", and the
# key of one class label per sample that the partition reads
INPUT_KEY = "images"
PARTITION_KEY = "labels"
# ``flops_per_sample`` over XLA's CPU count of one sample's loss and
# gradient: the dense count against XLA's, which leaves out the zero
# border of a "SAME" convolution (see ``flops_per_sample``)
XLA_FLOPS_RATIO = (1.08, 1.16)


def _conv(k: int, cin: int, cout: int) -> Tuple[int, ...]:
    return (k, k, cin, cout)


def param_shapes(m: Dict) -> PyTree:
    """The parameter tree as shapes: ``("conv", shape)``, ``("ones", c)``,
    ``("zeros", c)`` or ``("dense", shape)`` leaves."""
    def gn(c):
        return {"gamma": ("ones", (c,)), "beta": ("zeros", (c,))}

    tree: Dict[str, Any] = {
        "stem": ("conv", _conv(3, m["in_channels"], m["stem_channels"]))}
    cin = m["stem_channels"]
    groups: List[List[Dict]] = []
    for cout, n_blocks in zip(m["group_channels"], m["blocks_per_group"]):
        blocks = []
        for bi in range(n_blocks):
            c_in = cin if bi == 0 else cout
            block = {"gn1": gn(c_in), "conv1": ("conv", _conv(3, c_in, cout)),
                     "gn2": gn(cout), "conv2": ("conv", _conv(3, cout, cout))}
            if c_in != cout:
                block["proj"] = ("conv", _conv(1, c_in, cout))
            blocks.append(block)
        groups.append(blocks)
        cin = cout
    tree["groups"] = groups
    tree["head_gn"] = gn(cin)
    tree["head_w"] = ("dense", (cin, m["num_classes"]))
    tree["head_b"] = ("zeros", (m["num_classes"],))
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str)


def num_params(m: Dict) -> int:
    leaves = jax.tree_util.tree_leaves(param_shapes(m), is_leaf=_is_spec)
    return int(sum(math.prod(s) for _, s in leaves))


def init_params(m: Dict, key: jax.Array) -> PyTree:
    """He-normal convolutions (fan-in k*k*cin), a 1/sqrt(fan-in) head,
    unit GroupNorm scales and zero shifts and bias; float32. One jitted
    call on the default device."""
    spec = param_shapes(m)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_spec)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (kind, shape) in zip(keys, leaves):
            if kind == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            elif kind == "zeros":
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                fan_in = math.prod(shape[:-1])
                std = math.sqrt(2.0 / fan_in) if kind == "conv" \
                    else 1.0 / math.sqrt(fan_in)
                out.append(std * jax.random.normal(k, shape, jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)


# --------------------------------------------------------------------------- #
# plain reference
# --------------------------------------------------------------------------- #
def _group_norm(x, gamma, beta, groups: int, eps: float = 1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return xg.reshape(b, h, w, c) * gamma + beta


def _conv2d(x, w, stride: int, precision):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def logits(params: PyTree, images: jax.Array, m: Dict,
           precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """Forward pass in the dtype of ``params``: stem convolution, then per
    block GroupNorm -> ReLU -> conv (stride 2 opening groups 2-4, a 1x1
    projection where the width changes) -> GroupNorm -> ReLU -> conv plus
    the shortcut; GroupNorm -> ReLU -> global average pool -> dense."""
    dt = params["stem"].dtype
    g = m["gn_groups"]
    x = _conv2d(images.astype(dt), params["stem"], 1, precision)
    for gi, blocks in enumerate(params["groups"]):
        for bi, bp in enumerate(blocks):
            stride = 2 if (gi > 0 and bi == 0) else 1
            h = jax.nn.relu(_group_norm(x, bp["gn1"]["gamma"],
                                        bp["gn1"]["beta"], g))
            if "proj" in bp:
                shortcut = _conv2d(h, bp["proj"], stride, precision)
            elif stride != 1:
                shortcut = x[:, ::stride, ::stride, :]
            else:
                shortcut = x
            h = _conv2d(h, bp["conv1"], stride, precision)
            h = jax.nn.relu(_group_norm(h, bp["gn2"]["gamma"],
                                        bp["gn2"]["beta"], g))
            x = shortcut + _conv2d(h, bp["conv2"], 1, precision)
    x = jax.nn.relu(_group_norm(x, params["head_gn"]["gamma"],
                                params["head_gn"]["beta"], g))
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["head_w"], precision=precision) \
        + params["head_b"]


def loss(params: PyTree, images: jax.Array, labels: jax.Array, m: Dict,
         precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """Mean softmax cross-entropy, reduced in float32."""
    z = logits(params, images, m, precision).astype(jnp.float32)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# --------------------------------------------------------------------------- #
# model FLOPs
# --------------------------------------------------------------------------- #
def conv_layers(m: Dict) -> List[Tuple[int, int, int, int]]:
    """(output side, kernel side, cin, cout) of every convolution, in
    forward order, for a square ``image_size`` input."""
    side = m["image_size"]
    out = [(side, 3, m["in_channels"], m["stem_channels"])]
    cin = m["stem_channels"]
    for gi, (cout, n_blocks) in enumerate(zip(m["group_channels"],
                                              m["blocks_per_group"])):
        for bi in range(n_blocks):
            c_in = cin if bi == 0 else cout
            if gi > 0 and bi == 0:
                side = -(-side // 2)
            out.append((side, 3, c_in, cout))
            out.append((side, 3, cout, cout))
            if c_in != cout:
                out.append((side, 1, c_in, cout))
        cin = cout
    return out


def flops_per_sample(m: Dict) -> int:
    """Model FLOPs of one image's forward and backward pass: 2 per
    multiply-add of every convolution and of the head, forward once and
    backward twice (the gradients of the input and of the weights), less
    the input gradient of the stem, which no step needs. GroupNorm, ReLU,
    pooling and the loss are left out: they are elementwise and under 1%
    of the total.

    XLA's CPU ``cost_analysis`` of the same step reads about 10% lower
    (1.359e9 against 1.517e9 per image at the paper's widths, 2.917e9
    against 3.329e9 for ResNet-18): it counts only the multiply-adds whose
    input lies inside the image, not those over the zero border of a
    "SAME" convolution, which at a 4x4 map are 31% of a 3x3 kernel's
    taps. The forward pass alone shows it (4.517e8 against 5.069e8),
    with XLA's backward at 2.0 times its forward as here. The dense count
    is what the chip's matrix units execute, and it is the usual one."""
    layers = conv_layers(m)
    fwd = sum(2 * s * s * k * k * ci * co for s, k, ci, co in layers)
    fwd += 2 * m["group_channels"][-1] * m["num_classes"]
    side, k, ci, co = layers[0]
    return 3 * fwd - 2 * side * side * k * k * ci * co


def quant_entries(m: Dict, block: Tuple[int, int] = (256, 256)) -> int:
    """Entries of one device's gradient that the quantizer kernel takes
    per round: those of each leaf of two or more dimensions which, viewed
    as (prod(leading), last), splits into whole (min(256, rows),
    min(256, cols)) tiles; the other leaves are quantized outside it."""
    total = 0
    for _, shape in jax.tree_util.tree_leaves(param_shapes(m),
                                              is_leaf=_is_spec):
        if len(shape) < 2:
            continue
        rows, cols = math.prod(shape[:-1]), shape[-1]
        if rows % min(block[0], rows) == 0 and \
                cols % min(block[1], cols) == 0:
            total += rows * cols
    return total


# --------------------------------------------------------------------------- #
# the system under test
# --------------------------------------------------------------------------- #
def program_model(m: Dict):
    """The program's ResNet at these sizes."""
    from repro.configs.ltfl_paper import ResNetConfig
    from repro.models import resnet
    if m["gn_groups"] != resnet.GN_GROUPS:
        raise ValueError(f"the program fixes {resnet.GN_GROUPS} GroupNorm "
                         f"groups; the configuration asks for "
                         f"{m['gn_groups']}")
    return resnet.ResNet(ResNetConfig(
        image_size=m["image_size"], in_channels=m["in_channels"],
        num_classes=m["num_classes"], stem_channels=m["stem_channels"],
        group_channels=tuple(m["group_channels"]),
        blocks_per_group=tuple(m["blocks_per_group"])))
