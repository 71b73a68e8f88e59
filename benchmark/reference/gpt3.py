"""Plain reference of the GPT-3 block (Brown et al. 2020, arXiv:2005.14165,
section 2.1: the GPT-2 architecture, pre-normalisation): LayerNorm, fused
qkv projection, causal softmax attention, output projection, LayerNorm,
GELU feed-forward, all with biases; float32 at "highest" matmul precision,
no kernels, no cache, no batching.

Departures from the paper, which are the program's and are listed in the
configuration file under ``assumed``: every layer attends densely (the paper
alternates dense and locally banded layers); no learned position embedding
and no final LayerNorm, because ``TokenServingModel`` has neither (the causal
mask is the only position signal); the readout is a separate matrix (the
program reads out against the embedding rolled by one row).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def weights_of(tsm) -> dict:
    """The program's weights as plain arrays. ``tsm`` is the program's
    ``TokenServingModel``; layout ``[in, out]`` for every matrix."""
    core = getattr(tsm.core, "base", tsm.core)
    names = ("ln", "qkv", "out_proj", "ffn_ln", "ffn1", "ffn2")
    return {
        "embed": tsm._embed_np,
        "head": tsm.lm_head.data,
        "layers": [{n: (getattr(blk, n).weight.data, getattr(blk, n).bias.data)
                    for n in names} for blk in core.layers],
    }


def _layer_norm(x, wb):
    w, b = wb
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _linear(x, wb):
    w, b = wb
    return x @ w + b


def _block(x, lw, heads):
    n, d = x.shape
    q, k, v = jnp.split(_linear(_layer_norm(x, lw["ln"]), lw["qkv"]), 3, -1)
    q, k, v = (a.reshape(n, heads, d // heads).swapaxes(0, 1)
               for a in (q, k, v))
    s = q @ k.swapaxes(1, 2) / np.sqrt(d // heads)
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    a = (jax.nn.softmax(s, -1) @ v).swapaxes(0, 1).reshape(n, d)
    x = x + _linear(a, lw["out_proj"])
    h = jax.nn.gelu(_linear(_layer_norm(x, lw["ffn_ln"]), lw["ffn1"]),
                    approximate=False)
    return x + _linear(h, lw["ffn2"])


def logits(weights: dict, tokens, *, heads: int) -> np.ndarray:
    """Logits ``[len(tokens), vocab]`` of one sequence's full forward."""
    block = jax.jit(_block, static_argnames="heads")
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(weights["embed"][np.asarray(tokens)], jnp.float32)
        for lw in weights["layers"]:
            x = block(x, lw, heads=heads)
        return np.asarray(jax.jit(jnp.matmul)(x, weights["head"]))
