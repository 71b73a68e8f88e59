"""Plain reference of the ``lfm2_moe`` decoder (LiquidAI LFM2-24B-A2B,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json): float32
``jax.numpy`` at "highest" matmul precision, one sequence's full forward, no
kernel, no cache, no state, no batching. A conv layer is a plain causal
depthwise convolution over the WHOLE sequence; attention keeps K and V per
head.

    h0 = E[ids]
    layer l, kind t_l in layer_types (pre-norm):
      a = RMSNorm_op(h)
      t_l conv:  [B | C | x] = a W_in;  u = B * x
        y_t = w[:, 0] u_{t-2} + w[:, 1] u_{t-1} + w[:, 2] u_t   (u_t = 0, t < 0)
        o = (C * y) W_out
      t_l full_attention:  q, k, v = a Wq, a Wk, a Wv
        q, k = RMSNorm_q(q), RMSNorm_k(k)   over the head dimension
        RoPE (theta, whole head, half-split) on q, k; causal mask
        each KV head serves num_attention_heads / num_key_value_heads heads
        o = softmax(q k^T / sqrt(head_dim)) v Wo
      h = h + o;  m = RMSNorm_ffn(h);  h = h + F(m)
    F, first num_dense_layers layers: (silu(m W1) * (m W3)) W2
    F, the others: s = sigmoid(m Wr); S = top-k of s + b; w_e = s_e /
      (sum_{e in S} s_e + 1e-6) * routed_scaling_factor;
      F(m) = sum_{e in S} w_e expert_e(m)         (no shared expert)
    logits = RMSNorm_final(h_L) W_head

The engine (``paddle_tpu/inference/decoder.py``) serves the same layer in
pieces: a conv layer sees a prompt in chunks beside other slots' rows and
takes each row's two predecessors from earlier rows of its chunk or from the
slot's rows in the cache's state store; attention reads K and V two heads a
128-lane pool row through the paged kernel. That the two take different
routes to the same numbers is what makes the comparison independent.
Departures from the published model, each the configuration's and listed
there under ``assumed``, ``reduced`` or ``not_here``:

- the weights are the engine's own, seeded and rounded to its stored type
  (``weights_of``), upcast here; the engine keeps q|k|v as one matrix and
  gate|up as one: split here;
- the depth is the configuration's cut; the head is untied;
- routing is discrete: where this reference's own margin between the last
  chosen and the first unchosen expert (in ``s + b``) is under ``TIE_EPS``
  it takes the engine's choice for that row (``engine_routes``), counts it,
  and refuses any other disagreement (``stats``).

To fit beside the engine's weights on one chip it works a layer at a time,
a KV head and a block of rows at a time, one expert at a time, and
multiplies by the head only the rows that are asked for, a block of its
columns at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.afmoe import (ROW_BLOCK, _attention, _choose, _f32,
                                       _rms, _rope, _scores, _swiglu,
                                       weights_of)

__all__ = ["logits", "weights_of", "TIE_EPS", "VARIANTS"]

# margin in s + b under which two experts count as tied. Top 4 of 64
# sigmoid scores lie further apart than top 4 of 256 (the 4th score sits
# near 0.82, slope 0.15; the 4th and 5th about 1.8e-2 apart on average), but
# the engine's scores lie further from these too: nine layers of bfloat16
# activations put its residual stream 1.1e-2 from this one (relative L2;
# 6e-3 in the other two archs' cuts), and at that slope its scores deviate
# by about 2e-3 (standard deviation). On the chip the two disagreed in 571
# of 16 416 rows of the first seed, across at most 7.4e-3 (PERF.md, Findings,
# PR 35). 1.5e-2 is seven of those deviations and twice the widest gap seen:
# a tie is never refused by chance, and an expert chosen across a wider gap
# is a fault.
TIE_EPS = 1.5e-2
HEAD_COLUMNS = 16384    # columns of the head multiplied at a time
# the prompt chunk whose starts the ``no_state_carry`` variant forgets at:
# the cell's prefill budget, so the probe's 2 048-token prompt crosses one
CARRY_CHUNK = 1024
VARIANTS = (None, "no_state_carry", "taps_reversed", "gate_order",
            "rope_skipped", "no_qk_norm", "bias_as_weight")


@functools.partial(jax.jit, static_argnames=("cfg", "rounding", "variant",
                                             "carry_chunk"))
def _conv(x, p, *, cfg, rounding, variant, carry_chunk):
    """The gated short convolution over the whole sequence ``x`` [n, d]:
    returns ``(C * y) W_out``."""
    n = x.shape[0]
    a = _rms(x, p["in_norm"], cfg.rms_norm_eps)
    b, c, xg = jnp.split(a @ _f32(p["conv_in"], rounding), 3, axis=-1)
    if variant == "gate_order":              # C, B, x
        b, c = c, b
    u = b * xg
    taps = _f32(p["conv_taps"], rounding)
    if variant == "taps_reversed":
        taps = taps[:, ::-1]
    kernel = taps.shape[1]
    pos = jnp.arange(n)
    y = jnp.zeros_like(u)
    for j in range(kernel):                  # the row j back
        back = jnp.pad(u, ((j, 0), (0, 0)))[:n]
        if variant == "no_state_carry":      # a chunk starts from zeros
            back = jnp.where((pos % carry_chunk >= j)[:, None], back, 0.0)
        y = y + back * taps[:, kernel - 1 - j]
    return (c * y) @ _f32(p["conv_out"], rounding)


@functools.partial(jax.jit, static_argnames=("cfg", "rounding", "variant"))
def _qkv(x, p, *, cfg, rounding, variant):
    n = x.shape[0]
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    a = _rms(x, p["in_norm"], cfg.rms_norm_eps)
    q, k, v = jnp.split(a @ _f32(p["qkv"], rounding),
                        [nh * hd, (nh + nkv) * hd], axis=-1)
    q, k = q.reshape(n, nh, hd), k.reshape(n, nkv, hd)
    if variant != "no_qk_norm":
        q = _rms(q, p["q_norm"], cfg.rms_norm_eps)
        k = _rms(k, p["k_norm"], cfg.rms_norm_eps)
    if variant != "rope_skipped":
        pos = jnp.arange(n)
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    return q, k, v.reshape(n, nkv, hd)


@functools.partial(jax.jit, static_argnames=("cfg", "rounding", "w_out"))
def _residual(x, o, p, *, cfg, rounding, w_out=None):
    h = x + (o if w_out is None else o @ _f32(p[w_out], rounding))
    return h, _rms(h, p["pre_mlp_norm"], cfg.rms_norm_eps)


def _moe(m, p, cfg, rounding, engine_idx, tie_eps, stats, variant):
    k = cfg.num_experts_per_tok
    s, sel = _scores(m, p, cfg=cfg, rounding=rounding)
    idx = jnp.asarray(_choose(sel, k, engine_idx, tie_eps, stats))
    w = jnp.take_along_axis(sel if variant == "bias_as_weight" else s,
                            idx, -1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + cfg.route_norm_eps)
    w = w * cfg.route_scale
    f = jnp.zeros_like(m)
    for e in range(cfg.experts_held):        # one expert's weights at a time
        we = jnp.sum(jnp.where(idx == cfg.expert_offset + e, w, 0.0), -1)
        f = f + we[:, None] * _swiglu(m, p["experts_gate_up"][e],
                                      p["experts_down"][e],
                                      rounding=rounding)
        f.block_until_ready()
    return f


def logits(weights: dict, tokens, *, rows=None, engine_routes=None,
           tie_eps: float = TIE_EPS, stats: dict | None = None,
           rounding: int | None = None, variant: str | None = None,
           carry_chunk: int = CARRY_CHUNK) -> np.ndarray:
    """Logits ``[len(tokens), vocab]`` of one sequence's full forward, or
    ``[len(rows), vocab]``: those of the positions ``rows`` alone.
    ``engine_routes``: {layer: int array [len(tokens), k]}, the engine's
    chosen experts by position, consulted at near-ties only. ``rounding``
    keeps that many mantissa bits of every matrix (the reading that has to
    fail: 3 is a scaled float8 e4m3). ``variant`` breaks one mechanism (the
    tests' proof that the comparison would notice); ``no_state_carry``
    forgets the convolution's past at every multiple of ``carry_chunk``."""
    assert variant in VARIANTS, variant
    cfg = weights["config"]
    stats = {} if stats is None else stats
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(weights["embed"][np.asarray(tokens)], jnp.float32) \
            * np.float32(weights["input_scale"])
        for i, p in enumerate(weights["layers"]):
            if cfg.layer_types[i] == "conv":
                h, m = _residual(
                    x, _conv(x, p, cfg=cfg, rounding=rounding,
                             variant=variant, carry_chunk=int(carry_chunk)),
                    p, cfg=cfg, rounding=rounding)
            else:
                q, k, v = _qkv(x, p, cfg=cfg, rounding=rounding,
                               variant=variant)
                h, m = _residual(x, _attention(q, k, v, None), p, cfg=cfg,
                                 rounding=rounding, w_out="o")
            if cfg.is_moe(i):
                f = _moe(m, p, cfg, rounding,
                         None if engine_routes is None
                         else engine_routes[i], tie_eps, stats, variant)
            else:
                f = jnp.concatenate(
                    [_swiglu(m[lo:lo + ROW_BLOCK], p["gate_up"], p["down"],
                             rounding=rounding)
                     for lo in range(0, m.shape[0], ROW_BLOCK)], 0)
            x = h + f
        if rows is not None:
            x = x[np.asarray(rows)]
        x = _rms(x, weights["final_norm"], weights["norm_eps"])
        head = weights["head"]       # a block of columns at a time: the
        return np.concatenate(       # whole head upcast would be 0.5 GB
            [np.asarray(jax.jit(jnp.matmul)(
                x, _f32(head[:, lo:lo + HEAD_COLUMNS], rounding)))
             for lo in range(0, head.shape[1], HEAD_COLUMNS)], -1)
