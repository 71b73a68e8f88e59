"""Plain reference of the ``joyai_llm_flash`` decoder (JoyAI-LLM-Flash,
https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json;
the layer is DeepSeek-V3's): float32 ``jax.numpy`` at "highest" matmul
precision, one sequence's full forward, no kernel, no cache, no batching,
and multi-head latent attention in its UN-absorbed form: K and V are
decompressed per head and attention is 32 heads of 192 / 128.

    h0 = E[ids]
    layer l (pre-norm):
      a = RMSNorm_in(h)
      c_q = RMSNorm(a W_qa);  [q_nope | q_rope]_head = c_q W_qb
      [c_kv | k_r] = a W_kva;  c = RMSNorm(c_kv)
      [k_nope | v]_head = c W_kvb(head)
      RoPE (theta, interleaved pairs (2i, 2i+1)) on q_rope of every head
        and on k_r, the ONE rope head every query head shares
      score = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope), causal
      h = h + concat_heads(softmax(score) v) W_o
      m = RMSNorm_pre_mlp(h);  h = h + F(m)
    F, first first_k_dense_replace layers: (silu(m Wgate) * (m Wup)) Wdown
    F, the others: s = sigmoid(m Wr); S = top-k of s + b (noaux_tc at one
      group); w_e = s_e / (sum_{e in S} s_e + 1e-20) * routed_scaling_factor;
      F(m) = shared(m) + sum_{e in S} w_e expert_e(m)
    logits = RMSNorm_final(h_L) W_head

The engine (``paddle_tpu/inference/decoder.py``) ABSORBS: it caches ``[c |
RoPE(k_r)]``, multiplies q_nope by W_kvb's key half and the attention's
output by its value half, and never forms k_nope or v. That the two take
different routes to the same numbers is what makes the comparison
independent. Departures from the published model, each the configuration's
and listed there under ``assumed``, ``reduced`` or ``not_here``:

- the weights are the engine's own, seeded and rounded to its stored type
  (``weights_of``), upcast here; the engine holds W_kvb per head and split
  (key half transposed), gate|up as one matrix: re-assembled here;
- the depth is the configuration's cut; the multi-token-prediction layer is
  not run (it adds nothing to the logits);
- routing is discrete: where this reference's own margin between the last
  chosen and the first unchosen expert (in ``s + b``) is under ``TIE_EPS``
  it takes the engine's choice for that row (``engine_routes``), counts it,
  and refuses any other disagreement (``stats``).

To fit beside the engine's weights on one chip it works a layer at a time,
a group of heads and a block of rows at a time, one expert at a time, and
multiplies by the head only the rows that are asked for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.afmoe import (ROW_BLOCK, _choose, _f32, _rms,
                                       _scores, _swiglu, weights_of)

__all__ = ["logits", "weights_of", "TIE_EPS", "VARIANTS"]

# margin in s + b under which two experts count as tied. Top 8 of 256
# sigmoid scores lie closer than trinity's top 4 (the 8th and 9th 6.5e-3
# apart on average, at a score of about 0.87 with slope 0.11), and the
# engine's scores sit within about 1e-3 of these (bfloat16 activations, a
# residual stream some 5e-3 apart): 5e-3 is five of those deviations, so a
# tie is never refused by chance and an expert chosen across a wider gap
# is a fault. On the chip the two disagreed in 643 to 675 of 16 400 rows,
# never across more than 2.6e-3 (PERF.md, Findings, PR 32).
TIE_EPS = 5e-3
HEAD_GROUP = 8          # heads scored at a time
HEAD_COLUMNS = 16384    # columns of the head multiplied at a time
VARIANTS = (None, "rope_on_nope", "rope_half_split", "scale_576",
            "no_kv_norm", "bias_as_weight", "no_route_scale", "no_shared")


def _rope(x, pos, theta, half_split=False):
    """x [n, heads, rd]: dimension 2i pairs with 2i + 1 (``half_split``,
    a variant: i with i + rd/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if half_split:
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("cfg", "rounding", "variant"))
def _qkv(x, p, *, cfg, rounding, variant):
    """Un-absorbed operands: q [n, nh, nope + rd], k the same, v
    [n, nh, vd]."""
    n = x.shape[0]
    nh, nope, rd, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                          cfg.qk_rope_head_dim, cfg.kv_lora_rank)
    eps, pos = cfg.rms_norm_eps, jnp.arange(n)
    rope = functools.partial(_rope, pos=pos, theta=cfg.rope_theta,
                             half_split=variant == "rope_half_split")
    a = _rms(x, p["in_norm"], eps)
    c_q = _rms(a @ _f32(p["q_a"], rounding), p["q_a_norm"], eps)
    q = (c_q @ _f32(p["q_b"], rounding)).reshape(n, nh, nope + rd)
    kva = a @ _f32(p["kv_a"], rounding)
    c = kva[:, :rank]
    if variant != "no_kv_norm":
        c = _rms(c, p["kv_a_norm"], eps)
    # the engine's kv_b_k is W_kvb^K(head)^T, [nh, nope, rank]
    k_nope = jnp.einsum("nr,hdr->nhd", c, _f32(p["kv_b_k"], rounding))
    v = jnp.einsum("nr,hrv->nhv", c, _f32(p["kv_b_v"], rounding))
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:])
    k_rope = jnp.broadcast_to(rope(kva[:, None, rank:]), (n, nh, rd))
    if variant == "rope_on_nope":
        q_nope, k_nope = rope(q_nope), rope(k_nope)
    return (jnp.concatenate([q_nope, q_rope], -1),
            jnp.concatenate([k_nope, k_rope], -1), v)


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend(q, k, v, q0, *, scale):
    """A group of heads: q [rows, g, hd] at positions q0 .., k [n, g, hd],
    v [n, g, vd]."""
    rows, n = q.shape[0], k.shape[0]
    s = jnp.einsum("rgd,ngd->grn", q, k) * scale
    ok = jnp.arange(n)[None, :] <= q0 + jnp.arange(rows)[:, None]
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
    return jnp.einsum("grn,ngv->rgv", p, v)


def _attention(q, k, v, scale):
    n, nh, _ = q.shape
    out = []
    for h in range(0, nh, HEAD_GROUP):
        hs = slice(h, h + HEAD_GROUP)
        out.append(jnp.concatenate(
            [_attend(q[lo:lo + ROW_BLOCK, hs], k[:, hs], v[:, hs], lo,
                     scale=scale) for lo in range(0, n, ROW_BLOCK)], 0))
    return jnp.concatenate(out, 1).reshape(n, -1)


@functools.partial(jax.jit, static_argnames=("cfg", "rounding"))
def _after_attention(x, attn, p, *, cfg, rounding):
    h = x + attn @ _f32(p["o"], rounding)
    return h, _rms(h, p["pre_mlp_norm"], cfg.rms_norm_eps)


def _moe(m, p, cfg, rounding, engine_idx, tie_eps, stats, variant):
    k = cfg.num_experts_per_tok
    s, sel = _scores(m, p, cfg=cfg, rounding=rounding)
    idx = jnp.asarray(_choose(sel, k, engine_idx, tie_eps, stats))
    w = jnp.take_along_axis(sel if variant == "bias_as_weight" else s,
                            idx, -1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    if variant != "no_route_scale":
        w = w * cfg.route_scale
    f = jnp.zeros_like(m)
    if cfg.num_shared_experts and variant != "no_shared":
        f = f + _swiglu(m, p["shared_gate_up"], p["shared_down"],
                        rounding=rounding)
    for e in range(cfg.experts_held):        # one expert's weights at a time
        we = jnp.sum(jnp.where(idx == cfg.expert_offset + e, w, 0.0), -1)
        f = f + we[:, None] * _swiglu(m, p["experts_gate_up"][e],
                                      p["experts_down"][e],
                                      rounding=rounding)
        f.block_until_ready()
    return f


def logits(weights: dict, tokens, *, rows=None, engine_routes=None,
           tie_eps: float = TIE_EPS, stats: dict | None = None,
           rounding: int | None = None,
           variant: str | None = None) -> np.ndarray:
    """Logits ``[len(tokens), vocab]`` of one sequence's full forward, or
    ``[len(rows), vocab]``: those of the positions ``rows`` alone.
    ``engine_routes``: {layer: int array [len(tokens), k]}, the engine's
    chosen experts by position, consulted at near-ties only. ``rounding``
    keeps that many mantissa bits of every matrix (the reading that has to
    fail: 3 is a scaled float8 e4m3). ``variant`` breaks one mechanism (the
    tests' proof that the comparison would notice)."""
    assert variant in VARIANTS, variant
    cfg = weights["config"]
    stats = {} if stats is None else stats
    scale = float((cfg.kv_lora_rank + cfg.qk_rope_head_dim) ** -0.5
                  if variant == "scale_576" else cfg.attn_scale)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(weights["embed"][np.asarray(tokens)], jnp.float32) \
            * np.float32(weights["input_scale"])
        for i, p in enumerate(weights["layers"]):
            q, k, v = _qkv(x, p, cfg=cfg, rounding=rounding, variant=variant)
            h, m = _after_attention(x, _attention(q, k, v, scale), p,
                                    cfg=cfg, rounding=rounding)
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
        return np.concatenate(       # whole head upcast would be 1 GB
            [np.asarray(jax.jit(jnp.matmul)(
                x, _f32(head[:, lo:lo + HEAD_COLUMNS], rounding)))
             for lo in range(0, head.shape[1], HEAD_COLUMNS)], -1)
