"""Plain reference of the ``afmoe`` decoder (Arcee Trinity,
https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json):
float32 ``jax.numpy`` at "highest" matmul precision, one sequence's full
forward, no kernel, no cache, no batching.

    h0 = E[ids] * sqrt(d)                               (mup_enabled)
    layer l, type t_l in layer_types:
      a = RMSNorm_in(h);  q, k, v, g = a Wq, a Wk, a Wv, a Wg
      q, k = RMSNorm_q(q), RMSNorm_k(k)   over the head dimension
      t_l sliding: RoPE (theta, whole head, half-split) on q, k, and key j
        is visible to query i iff 0 <= i - j < sliding_window;
      t_l full: no position encoding, causal mask only
      each KV head serves num_attention_heads / num_key_value_heads heads
      o = (softmax(q k^T / sqrt(head_dim)) v * sigmoid(g)) Wo
      h = h + RMSNorm_post_attn(o)
      m = RMSNorm_pre_mlp(h);  h = h + RMSNorm_post_mlp(F(m))
    F, first num_dense_layers layers: (silu(m Wgate) * (m Wup)) Wdown
    F, the others: s = sigmoid(m Wr); S = top-k of s + b; w_e = s_e /
      (sum_{e in S} s_e + 1e-20) * route_scale;
      F(m) = shared(m) + sum_{e in S} w_e expert_e(m)
    logits = RMSNorm_final(h_L) W_head

Departures from the published model, each the configuration's and listed
there under ``assumed`` or ``reduced``:

- the weights are the engine's own, seeded and rounded to its stored type
  (``weights_of``), upcast here: the comparison is of the arithmetic;
- only the experts ``[expert_offset, expert_offset + experts_held)`` add to
  ``F``: what the other chips of the deployment would add is left out, here
  as in the engine (the router still scores all ``num_experts``);
- the engine keeps q|k|v|g as one matrix and gate|up as one: split here;
- the vocabulary is the chip's slice, the depth the configuration's cut;
- routing is discrete: where this reference's own margin between the last
  chosen and the first unchosen expert (in ``s + b``) is under ``TIE_EPS``
  it takes the engine's choice for that row (``engine_routes``), counts it,
  and refuses any other disagreement (``stats``).

To fit beside the engine's weights on one chip it works a layer at a time,
a KV-head group and a block of rows at a time, one expert at a time (and
waits for each, so that one expert's upcast weights and results are alive
whatever the runtime would dispatch ahead), and multiplies by the head only
the rows that are asked for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# margin in s + b under which two experts count as tied. The engine's
# router sees bfloat16 activations and a residual stream that already
# differs from this one's by some 6e-3 (relative L2): near the top-4
# threshold of 256 sigmoid scores (about 0.9, slope 0.09) its scores sit
# within about 1e-3 of these (standard deviation 0.9e-3, from the first
# chip run: of 695 rows of 24 592 in which the two chose differently, 8 did
# so across more than 2e-3), while the 4th and 5th scores lie 1.3e-2 apart
# on average. 1e-2 is ten of those deviations: a tie is never refused by
# chance, and an expert chosen across a wider gap is a fault.
TIE_EPS = 1e-2
ROW_BLOCK = 1024        # rows scored, or put through the dense FFN, at a time
VARIANTS = (None, "no_window", "rope_on_full", "bias_as_weight",
            "capacity_drop")


def weights_of(tsm) -> dict:
    """The program's weights as it stores them (``tsm`` is the program's
    ``TokenServingModel`` over a ``DecoderCore``)."""
    core = tsm.core
    return {"config": core.config, "embed": tsm._embed_np,
            "input_scale": tsm.input_scale, "final_norm": tsm.final_norm,
            "norm_eps": tsm.norm_eps, "head": tsm.lm_head.data,
            "layers": core.params}


def _f32(w, rounding=None):
    """``w`` upcast; with ``rounding`` (mantissa bits kept) first rounded
    to a lower precision at its own exponent, which is what a scaled 8-bit
    float format does to a weight: 3 bits is e4m3's mantissa. An explicit
    ``reduce_precision``: a pair of converts is something XLA may drop
    (``xla_allow_excess_precision``), and on the chip it did."""
    w = w.astype(jnp.float32)
    if rounding is not None:       # the lower-precision reading (PERF.md)
        w = jax.lax.reduce_precision(w, exponent_bits=8,
                                     mantissa_bits=int(rounding))
    return w


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """x [n, heads, hd]; dimension i pairs with i + hd/2."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "rope", "rounding"))
def _qkvg(x, p, *, cfg, rope, rounding):
    n = x.shape[0]
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    a = _rms(x, p["in_norm"], cfg.rms_norm_eps)
    q, k, v, g = jnp.split(
        a @ _f32(p["qkvg"], rounding),
        [nh * hd, (nh + nkv) * hd, (nh + 2 * nkv) * hd], axis=-1)
    q = _rms(q.reshape(n, nh, hd), p["q_norm"], cfg.rms_norm_eps)
    k = _rms(k.reshape(n, nkv, hd), p["k_norm"], cfg.rms_norm_eps)
    if rope:
        pos = jnp.arange(n)
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    return q, k, v.reshape(n, nkv, hd), g


@functools.partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, q0, *, window):
    """One KV head's group: q [rows, g, hd] at positions q0 .., k / v
    [n, hd]."""
    rows, n = q.shape[0], k.shape[0]
    s = jnp.einsum("rgd,nd->grn", q, k) / np.sqrt(q.shape[-1])
    qpos = q0 + jnp.arange(rows)[:, None]
    kpos = jnp.arange(n)[None, :]
    ok = kpos <= qpos
    if window is not None:
        ok = ok & (qpos - kpos < window)
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
    return jnp.einsum("grn,nd->rgd", p, v)


def _attention(q, k, v, window):
    n, nh, hd = q.shape
    nkv = k.shape[1]
    qg = q.reshape(n, nkv, nh // nkv, hd)
    out = []
    for h in range(nkv):
        out.append(jnp.concatenate(
            [_attend(qg[lo:lo + ROW_BLOCK, h], k[:, h], v[:, h], lo,
                     window=window) for lo in range(0, n, ROW_BLOCK)], 0))
    return jnp.stack(out, 1).reshape(n, nh * hd)


@functools.partial(jax.jit, static_argnames=("cfg", "rounding"))
def _after_attention(x, attn, g, p, *, cfg, rounding):
    o = (attn * jax.nn.sigmoid(g)) @ _f32(p["o"], rounding)
    h = x + _rms(o, p["post_attn_norm"], cfg.rms_norm_eps)
    return h, _rms(h, p["pre_mlp_norm"], cfg.rms_norm_eps)


@functools.partial(jax.jit, static_argnames=("rounding",))
def _swiglu(m, gate_up, down, *, rounding=None):
    gate, up = jnp.split(m @ _f32(gate_up, rounding), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ _f32(down, rounding)


@functools.partial(jax.jit, static_argnames=("cfg", "rounding"))
def _scores(m, p, *, cfg, rounding):
    s = jax.nn.sigmoid(m @ _f32(p["router"], rounding))
    return s, s + p["router_bias"][None, :]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _finish(h, f, p, *, cfg):
    return h + _rms(f, p["post_mlp_norm"], cfg.rms_norm_eps)


def _choose(sel, k, engine_idx, tie_eps, stats):
    """The chosen set [n, k] on the host: this reference's own top-k of
    ``sel`` = s + b, except in rows whose margin is under ``tie_eps``,
    where the engine's set stands if it differs only among the tied."""
    sel = np.asarray(sel)
    order = np.argsort(-sel, axis=-1, kind="stable")
    own = order[:, :k]
    if engine_idx is None:
        return own
    eng = np.asarray(engine_idx)
    # the experts only one of the two chose must all lie within the
    # margin of each other: the highest the engine left out against the
    # lowest it took instead
    own_only = ~(own[:, :, None] == eng[:, None, :]).any(-1)
    eng_only = ~(eng[:, :, None] == own[:, None, :]).any(-1)
    differs = own_only.any(-1)
    high = np.where(own_only, np.take_along_axis(sel, own, -1),
                    -np.inf).max(-1)
    low = np.where(eng_only, np.take_along_axis(sel, eng, -1),
                   np.inf).min(-1)
    tied = differs & (high - low < tie_eps)
    stats["route_rows"] = stats.get("route_rows", 0) + int(sel.shape[0])
    stats["route_ties_taken"] = stats.get("route_ties_taken", 0) \
        + int(tied.sum())
    stats["route_flips_outside_margin"] = \
        stats.get("route_flips_outside_margin", 0) \
        + int((differs & ~tied).sum())
    if differs.any():     # the widest gap across which the two disagreed
        stats["route_widest_gap"] = max(
            stats.get("route_widest_gap", 0.0),
            float((high - low)[differs].max()))
    return np.where(tied[:, None], eng, own)


def _moe(m, p, cfg, rounding, engine_idx, tie_eps, stats, variant):
    k = cfg.num_experts_per_tok
    s, sel = _scores(m, p, cfg=cfg, rounding=rounding)
    idx = jnp.asarray(_choose(sel, k, engine_idx, tie_eps, stats))
    chosen = jnp.take_along_axis(sel if variant == "bias_as_weight" else s,
                                 idx, -1)
    w = chosen
    if cfg.route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg.route_scale
    f = jnp.zeros_like(m)
    if cfg.num_shared_experts:
        f = f + _swiglu(m, p["shared_gate_up"], p["shared_down"],
                        rounding=rounding)
    for e in range(cfg.experts_held):        # one expert's weights at a time
        we = jnp.sum(jnp.where(idx == cfg.expert_offset + e, w, 0.0), -1)
        if variant == "capacity_drop":
            # a GShard capacity of 1.25 x the mean load: later rows lose
            cap = int(1.25 * m.shape[0] * k / cfg.num_experts) + 1
            we = jnp.where(jnp.cumsum(we > 0) <= cap, we, 0.0)
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
    fail: 3 is a scaled float8 e4m3). ``variant`` breaks one mechanism (the tests' proof that the
    comparison would notice)."""
    assert variant in VARIANTS, variant
    cfg = weights["config"]
    stats = {} if stats is None else stats
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(weights["embed"][np.asarray(tokens)], jnp.float32) \
            * np.float32(weights["input_scale"])
        for i, p in enumerate(weights["layers"]):
            sliding = cfg.window_of(i) is not None
            q, k, v, g = _qkvg(
                x, p, cfg=cfg, rounding=rounding,
                rope=sliding or variant == "rope_on_full")
            window = None if variant == "no_window" else cfg.window_of(i)
            h, m = _after_attention(x, _attention(q, k, v, window), g, p,
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
            x = _finish(h, f, p, cfg=cfg)
        if rows is not None:
            x = x[np.asarray(rows)]
        x = _rms(x, weights["final_norm"], weights["norm_eps"])
        return np.asarray(jax.jit(jnp.matmul)(
            x, _f32(weights["head"], rounding)))
