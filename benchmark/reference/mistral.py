"""Plain reference of the Mistral-7B decoder (Jiang et al. 2023,
arXiv:2310.06825; ``modeling_mistral.py`` of the published checkpoint):
RMSNorm, rotary positions in the half-rotation convention, grouped KV heads,
causal attention inside a sliding window, SwiGLU feed-forward, a final RMSNorm,
an untied readout, and the mean next-token cross-entropy. float32 at "highest"
matmul precision, one sequence at a time, no kernels, no sharding.

Departure, listed in the configuration files under ``assumed``: the program's
trainer has no sliding window; this reference applies the published one, which
at 2048 positions under a window of 4096 masks nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def weights_of(trainer) -> dict:
    """The trainer's parameters on its first device, layers in order.
    Block leaves arrive as ``[stages, layers per stage, ...]``."""
    dev = jax.devices()[0]
    p = trainer.params
    out = {k: jax.device_put(p[k], dev) for k in ("embed", "norm", "head")}
    out["blocks"] = {k: jax.device_put(v, dev).reshape((-1,) + v.shape[2:])
                     for k, v in p["blocks"].items()}
    return out


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    n, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _sequence_ce(w, ids, heads, kv_heads, theta, eps, window):
    """Sum over positions 0..n-2 of the cross-entropy of the next token."""
    f32 = lambda a: a.astype(jnp.float32)
    n = ids.shape[0]
    x = f32(w["embed"][ids])
    d = x.shape[-1]
    hd = d // heads
    pos = jnp.arange(n)
    seen = (pos[None, :] <= pos[:, None]) & (pos[:, None] - pos[None, :]
                                             < window)
    blocks = w["blocks"]
    for i in range(blocks["wq"].shape[0]):
        b = {k: f32(v[i]) for k, v in blocks.items()}
        h = _rms(x, b["ln1"], eps)
        q = _rope((h @ b["wq"]).reshape(n, heads, hd), theta)
        k = _rope((h @ b["wk"]).reshape(n, kv_heads, hd), theta)
        v = (h @ b["wv"]).reshape(n, kv_heads, hd)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        s = jnp.where(seen[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(n, d) @ b["wo"]
        h = _rms(x, b["ln2"], eps)
        x = x + (jax.nn.silu(h @ b["wg"]) * (h @ b["wu"])) @ b["wd"]
    logits = _rms(x, f32(w["norm"]), eps) @ f32(w["head"])
    logp = jax.nn.log_softmax(logits[:-1], -1)
    return -jnp.take_along_axis(logp, ids[1:, None], -1).sum()


def loss(weights: dict, ids, *, heads: int, kv_heads: int, theta: float,
         eps: float, window: int) -> float:
    """Mean next-token cross-entropy of the batch ``ids`` [B, T]."""
    ce = jax.jit(_sequence_ce, static_argnums=(2, 3, 4, 5, 6))
    ids = jax.device_put(jnp.asarray(ids), jax.devices()[0])
    with jax.default_matmul_precision("highest"):
        total = sum(float(ce(weights, row, heads, kv_heads, theta, eps,
                             window)) for row in ids)
    return total / (ids.shape[0] * (ids.shape[1] - 1))
