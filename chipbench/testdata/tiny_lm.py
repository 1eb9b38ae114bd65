"""A toy next-token family, for the benchmark's tests: its own seeded token
samples, a program model defined here, and the plain reference of its
loss.

The samples are documents of heavy-tailed length packed into rows of
``seq_len`` + 1 tokens: ``tokens`` the first ``seq_len``, ``labels`` the
next token at each position. The model embeds each token and maps it to
the vocabulary with one dense layer; the loss is the mean next-token
cross-entropy. The program model computes the same maths another way (a
one-hot product for the embedding, ``log_softmax``) and adds next-token
accuracy as its eval head.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

INPUT_KEY = "tokens"
# no class per sample: the partition reads the number of samples
PARTITION_KEY = None
_TOKENS = 16                # the samples' stream tag under the seed
# ``flops_per_sample`` over XLA's CPU count of one sample's loss and
# gradient: XLA also counts the softmax's elementwise work, about 10
# FLOPs a position and vocabulary entry, 5% of the total at width 32
XLA_FLOPS_RATIO = (0.93, 0.98)


def _rows(seed: int, num: int, m: Dict, split: int) -> np.ndarray:
    """(num, seq_len + 1) int32 rows of packed documents. Each document
    opens with token 0 and runs for 2 + floor(4 * Pareto(1.2)) tokens, at
    most 4 * seq_len; its other tokens step through 1..vocab-1 by 5 from
    a random start, plus a noise of 0, 1 or 2."""
    rng = np.random.default_rng([int(seed), _TOKENS, split])
    v, t = m["vocab_size"], m["seq_len"]
    total = num * (t + 1)
    lengths = np.minimum(2 + np.floor(4 * rng.pareto(1.2, total // 2 + 1)),
                         4 * t).astype(np.int64)
    lengths = lengths[:np.searchsorted(np.cumsum(lengths), total) + 1]
    doc = np.repeat(np.arange(lengths.size), lengths)[:total]
    pos = np.arange(total) - np.concatenate(
        [[0], np.cumsum(lengths)[:-1]])[doc]
    start = rng.integers(0, v - 1, lengths.size)
    noise = rng.integers(0, 3, total)
    toks = np.where(pos == 0, 0, 1 + (start[doc] + 5 * pos + noise) % (v - 1))
    return toks.reshape(num, t + 1).astype(np.int32)


def dataset(seed: int, cfg: Dict):
    """(train, test) as dicts of host arrays ``tokens`` and ``labels``,
    each (N, seq_len) int32."""
    m, dep = cfg["model"], cfg["deployment"]
    out = []
    for split, num in enumerate((dep["train_samples"], dep["test_samples"])):
        rows = _rows(seed, num, m, split)
        out.append({"tokens": rows[:, :-1], "labels": rows[:, 1:]})
    return tuple(out)


def num_params(m: Dict) -> int:
    v, w = m["vocab_size"], m["width"]
    return 2 * v * w + v


def init_params(m: Dict, key: jax.Array):
    """A unit-normal embedding, a 1/sqrt(width) dense layer and a zero
    bias, float32, in one jitted call."""
    v, w = m["vocab_size"], m["width"]

    def make(key):
        k_e, k_d = jax.random.split(key)
        return {"embed": jax.random.normal(k_e, (v, w), jnp.float32),
                "dense": jax.random.normal(k_d, (w, v), jnp.float32)
                / math.sqrt(w),
                "bias": jnp.zeros((v,), jnp.float32)}

    return jax.jit(make)(key)


def loss(params, tokens, labels, m: Dict,
         precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """Mean next-token cross-entropy, reduced in float32."""
    x = jnp.take(params["embed"], tokens, axis=0)
    z = (jnp.dot(x, params["dense"], precision=precision)
         + params["bias"]).astype(jnp.float32)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)


def flops_per_sample(m: Dict) -> int:
    """The dense layer's multiply-adds at every position, forward once
    and backward twice; the embedding is a gather."""
    return 6 * m["seq_len"] * m["width"] * m["vocab_size"]


class TinyLM:
    """The system under test: the same model, written apart."""

    def __init__(self, m: Dict):
        self.vocab = m["vocab_size"]

    def _logits(self, params, tokens):
        x = jax.nn.one_hot(tokens, self.vocab, dtype=jnp.float32) \
            @ params["embed"]
        return x @ params["dense"] + params["bias"]

    def loss(self, params, batch) -> jax.Array:
        logp = jax.nn.log_softmax(self._logits(params, batch["tokens"]))
        gold = jax.nn.one_hot(batch["labels"], self.vocab, dtype=jnp.float32)
        return -jnp.mean(jnp.sum(logp * gold, axis=-1))

    def accuracy(self, params, batch) -> jax.Array:
        pred = jnp.argmax(self._logits(params, batch["tokens"]), axis=-1)
        return jnp.mean((pred == batch["labels"]).astype(jnp.float32))


def program_model(m: Dict) -> TinyLM:
    return TinyLM(m)
