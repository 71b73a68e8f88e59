"""One config-driven decoder block behind the serving cache protocol.

``DecoderCore`` is the serving core for architectures the GPT-3 block of
``incubate/nn/fused_transformer.py`` cannot express: its configuration
speaks a published ``config.json``'s own keys, its layers have a type
each (``layer_types``), and the block is written ONCE, as a function of
(parameters, rows, positions, the layer's cache view, the layer's type)
— ``decoder_block`` below. The engines see the protocol they already
speak: ``core(x, caches=views, time_step=t) -> (hidden, views)`` with
``is_paged`` views (``inference/paged_cache.py``), so chunked prefill,
the prefix cache, the journal, snapshots and preemption hold unchanged.

The block has two statics, which ``DecoderConfig.from_spec`` derives from
the spec's ``arch``: the ATTENTION KIND (``gqa`` | ``mla``) and the
RESIDUAL FORM (``sandwich`` | ``pre_norm``). ``afmoe`` (Arcee Trinity) is
``gqa`` in a ``sandwich``:

    a = RMSNorm_in(h);  q, k, v, g = a Wq, a Wk, a Wv, a Wg
    q, k = RMSNorm_q(q), RMSNorm_k(k)            (over the head dim)
    sliding layers only: RoPE (half-split) on q, k; window W
    o = (softmax(q k^T / sqrt(hd), causal [and window]) v * sigmoid(g)) Wo
    h = h + RMSNorm_post_attn(o)
    m = RMSNorm_pre_mlp(h);  h = h + RMSNorm_post_mlp(F(m))

``joyai_llm_flash`` (DeepSeek-V3's layer) is ``mla`` in ``pre_norm``
(``h = h + attn(RMSNorm(h))``; ``h = h + F(RMSNorm(h))``), multi-head
latent attention in its ABSORBED form over a latent cache:

    c_q = RMSNorm(a W_qa);  [q_nope | q_rope]_head = c_q W_qb
    [c_kv | k_r] = a W_kva;  c = RMSNorm(c_kv);  RoPE on q_rope, k_r
        (interleaved pairs (2i, 2i+1), the rope dimensions only; k_r is
        ONE head, shared by all query heads)
    cached row = [c | RoPE(k_r) | 0]    (kv_lora_rank + qk_rope_head_dim,
        zero-padded to whole 128-lane tiles: ``DecoderConfig.kv_width``)
    q_abs_head = [q_nope W_kvb^K(head)^T | RoPE(q_rope) | 0]
    o_head = (softmax(q_abs . row / sqrt(nope + rope), causal) row[:rank])
             W_kvb^V(head);   h = h + concat_heads(o) W_o

so the pool holds ONE row a position a layer and no decompressed K or V
is ever written (``PagedKVCache``'s latent form; the view takes
``decode(q_abs, row, None, t)``). Every layer is a full layer.

``lfm2_moe`` (LiquidAI LFM2) is ``gqa`` in ``pre_norm`` with a LAYER KIND
a layer (``layer_types``: ``conv`` | ``full_attention``). What ``afmoe``
does to its attention is the arch's, not ``gqa``'s (``ARCH_FIELDS``):
here every attention layer rotates (half-split RoPE), none gates its
output, the router's normalising sum adds 1e-6, and there is no shared
expert. A ``conv`` layer holds NO K/V: it is a gated short convolution
over its own input,

    [B | C | x] = a W_in;  u = B * x
    y_t = sum_j taps[:, K-1-j] * u_{t-j},  j = 0 .. K-1  (u_t = 0, t < 0)
    h = h + (C * y) W_out

whose memory is the last ``K - 1`` rows of ``u`` a slot: the cache's
STATE STORE (``PagedKVCache(layer_state=)``), reached through the same
views (``view.mix(u, taps)``: predecessors from earlier rows of the
call or from the slot's stored rows, write-back of each slot's last
rows). Heads of 64 are cached two a 128-lane row (``kv_pack``): the pool
holds ``num_key_value_heads / 2`` heads of 128, a query head's operand
is zero in the other head's half, and the block takes its own half of
the launch's output.

``F`` is a SwiGLU in the first ``num_dense_layers`` layers and, after
them, a shared SwiGLU expert plus this chip's share of the routed ones
(``inference/moe_serving.py``: sigmoid scores over ALL experts, top-k of
score + bias, dropless grouped GEMM over the experts held here). With
``gqa`` each KV head serves ``num_attention_heads / num_key_value_heads``
query heads; the pool stores the KV heads only.

Precision: weights are stored in ``weight_dtype``; every product
accumulates in float32 and hands its result on in ``weight_dtype``; the
residual stream, the norm statistics, RoPE, the router's sigmoid and
top-k and the attention softmax are float32.

Weights are drawn ON THE DEVICE from the configuration's seed
(``jax.random``), expert-stacked ``[experts_held, ...]``: a host float32
copy of 4 B parameters would be 17 GB and a minute of ``randn``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor
from .moe_serving import (dropless_experts, expert_row_block,
                          sigmoid_route, swiglu)
from .paged_cache import PagedLayerCache

__all__ = ["DecoderConfig", "DecoderCore", "decoder_block", "rms_norm",
           "rope_half_split", "rope_interleaved"]

SLIDING, FULL, CONV = "sliding_attention", "full_attention", "conv"
# arch -> (attention kind, residual form)
ARCHS = {"afmoe": ("gqa", "sandwich"),
         "joyai_llm_flash": ("mla", "pre_norm"),
         "lfm2_moe": ("gqa", "pre_norm")}
# what else an arch fixes, as configuration fields (absent: afmoe's)
ARCH_FIELDS = {"lfm2_moe": {"rope_layers": "all", "attn_gate": False,
                            "route_norm_eps": 1e-6, "pack_kv_heads": True}}
LANES = 128     # a pool row is stored in whole lane tiles
# the published keys of an ``mla`` configuration (DeepSeek-V3's names) and
# the fields they fill
MLA_KEYS = {"first_k_dense_replace": "num_dense_layers",
            "n_routed_experts": "num_experts",
            "n_shared_experts": "num_shared_experts",
            "norm_topk_prob": "route_norm",
            "routed_scaling_factor": "route_scale"}
# the published keys of an ``lfm2_moe`` configuration that are not the
# fields' own names (``rope_parameters.rope_theta`` is read too)
LFM2_KEYS = {"norm_eps": "rms_norm_eps", "norm_topk_prob": "route_norm",
             "routed_scaling_factor": "route_scale"}


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The catalog's ``config.json`` keys, plus which experts live here
    (``experts_held`` of ``num_experts`` from ``expert_offset``), the
    stored weight type and the block's two statics. An ``mla`` spec
    speaks DeepSeek-V3's keys (``MLA_KEYS``, ``num_hidden_layers``, the
    five latent widths, ``rope_interleave``); an ``lfm2_moe`` spec its
    own (``LFM2_KEYS``, ``conv_L_cache``, ``use_expert_bias``,
    ``rope_parameters``; ``head_dim`` defaults to hidden / heads)."""
    hidden_size: int
    num_attention_heads: int
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    intermediate_size: int
    num_key_value_heads: int = 1
    head_dim: int = 0
    sliding_window: int = 0
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_intermediate_size: int = 0
    route_norm: bool = True
    route_scale: float = 1.0
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = False
    experts_held: Optional[int] = None
    expert_offset: int = 0
    weight_dtype: str = "bfloat16"
    attention: str = "gqa"
    residual: str = "sandwich"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = True
    conv_L_cache: int = 0          # a conv layer's kernel (taps a column)
    use_expert_bias: bool = True   # False: the selection bias is zero
    # the arch's own (``ARCH_FIELDS``), never a spec key
    rope_layers: str = "sliding"   # which attention layers rotate: | all
    attn_gate: bool = True         # sigmoid(a Wg) on the attention output
    route_norm_eps: float = 1e-20  # added to the chosen scores' sum
    pack_kv_heads: bool = False    # heads under 128 wide share a pool row

    KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "layer_types", "sliding_window",
            "num_dense_layers", "intermediate_size", "num_experts",
            "num_experts_per_tok", "num_shared_experts",
            "moe_intermediate_size", "route_norm", "route_scale",
            "rope_theta", "rms_norm_eps", "mup_enabled", "experts_held",
            "expert_offset", "weight_dtype", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_interleave", "conv_L_cache", "use_expert_bias")

    @classmethod
    def from_spec(cls, spec: dict) -> "DecoderConfig":
        kw = {k: spec[k] for k in cls.KEYS if spec.get(k) is not None}
        arch = spec.get("arch", "afmoe")
        kw["attention"], kw["residual"] = ARCHS[arch]
        kw.update(ARCH_FIELDS.get(arch, {}))
        if arch == "lfm2_moe":
            kw.update({field: spec[k] for k, field in LFM2_KEYS.items()
                       if spec.get(k) is not None})
            rope = spec.get("rope_parameters") or {}
            if "rope_theta" in rope:
                kw["rope_theta"] = rope["rope_theta"]
            kw.setdefault("head_dim", int(spec["hidden_size"])
                          // int(spec["num_attention_heads"]))
            if spec.get("conv_bias"):
                raise ValueError("conv_bias true is not built: the "
                                 "short convolution here has no bias")
        if kw["attention"] == "mla":
            kw.update({field: spec[k] for k, field in MLA_KEYS.items()
                       if spec.get(k) is not None})
            kw["layer_types"] = (FULL,) * int(spec["num_hidden_layers"])
            # the cache's geometry, not the published K/V heads: one
            # latent head a position
            kw["num_key_value_heads"] = 1
        kw["layer_types"] = tuple(kw["layer_types"])
        cfg = cls(**kw)
        held = cfg.num_experts if cfg.experts_held is None \
            else cfg.experts_held
        cfg = dataclasses.replace(cfg, experts_held=int(held))
        bad = set(cfg.layer_types) - {SLIDING, FULL, CONV}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if CONV in cfg.layer_types and (
                cfg.conv_L_cache < 2 or cfg.attention != "gqa"
                or cfg.residual != "pre_norm"):
            raise ValueError(
                "conv layers take conv_L_cache >= 2 (the kernel) in a "
                "gqa, pre_norm arch (lfm2_moe)")
        if cfg.attention == "mla":
            if min(cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                   cfg.qk_rope_head_dim, cfg.v_head_dim) < 1 \
                    or cfg.qk_rope_head_dim % 2:
                raise ValueError(
                    "an mla spec takes q_lora_rank, kv_lora_rank, "
                    "qk_nope_head_dim, v_head_dim and an even "
                    "qk_rope_head_dim")
            if not cfg.rope_interleave:
                raise ValueError("mla rotates interleaved pairs "
                                 "(rope_interleave true) only")
        elif cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        elif cfg.head_dim < 2 or cfg.head_dim % 2:
            raise ValueError("head_dim must be even (half-split RoPE)")
        if cfg.num_layers > cfg.num_dense_layers and not (
                0 < cfg.num_experts_per_tok <= cfg.num_experts and
                0 <= cfg.expert_offset and
                cfg.expert_offset + cfg.experts_held <= cfg.num_experts):
            raise ValueError(
                f"experts [{cfg.expert_offset}, "
                f"{cfg.expert_offset + cfg.experts_held}) of "
                f"{cfg.num_experts}, top {cfg.num_experts_per_tok}")
        return cfg

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def input_scale(self) -> float:
        """What the embedding rows are multiplied by (``mup_enabled``)."""
        return math.sqrt(self.hidden_size) if self.mup_enabled else 1.0

    def window_of(self, layer: int) -> Optional[int]:
        return self.sliding_window \
            if self.layer_types[layer] == SLIDING else None

    def is_moe(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    def rotates(self, layer: int) -> bool:
        """Whether attention layer ``layer`` carries RoPE."""
        return self.rope_layers == "all" \
            or self.layer_types[layer] == SLIDING

    @property
    def kv_pack(self) -> int:
        """KV heads stored side by side in one pool row: 128 / head_dim
        where the arch packs (``pack_kv_heads``) and the heads divide,
        else 1. A 64-wide pool row makes the TPU compiler lay the pool
        out block-axis-minor and copy it whole at every write, as a
        576-wide one does (tests/test_pool_write_hlo.py compiles both);
        two heads a row store the published bytes and no padding."""
        n = LANES // self.head_dim if self.pack_kv_heads \
            and 0 < self.head_dim < LANES and LANES % self.head_dim == 0 \
            else 1
        return n if self.num_key_value_heads % n == 0 else 1

    @property
    def kv_width(self) -> int:
        """Columns the cache STORES a position and kv head: the head
        (``gqa``); the latent and the shared rope head, padded with
        zeros to whole 128-lane tiles (``mla``: 576 -> 640); ``kv_pack``
        heads side by side (``gqa`` where the arch packs). A pool
        whose rows are not whole tiles is laid out by the TPU compiler
        with the BLOCK axis minor, and every page write and kernel launch
        then copies the whole pool there and back
        (tests/test_pool_write_hlo.py compiles both widths)."""
        if self.attention != "mla":
            return self.head_dim * self.kv_pack
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def attn_scale(self) -> float:
        """``mla``: over the UN-absorbed head, not the cached row."""
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            if self.attention == "mla" else self.head_dim ** -0.5


# ---------------------------------------------------------------------
# the block's pieces: pure functions of arrays
# ---------------------------------------------------------------------

def rms_norm(x, gain, eps):
    """``x / sqrt(mean(x^2) + eps) * gain`` over the last axis, in
    float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def rope_half_split(x, positions, theta):
    """Rotary embedding over the whole head, half-split pairing
    (dimension i rotates with i + hd/2): ``x`` [..., rows, heads, hd]
    float32, ``positions`` [..., rows] int32."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rope_interleaved(x, positions, theta):
    """Rotary embedding over the whole of ``x``'s last axis, interleaved
    pairing (dimension 2i rotates with 2i + 1): ``x`` [..., rows, heads,
    rd] float32, ``positions`` [..., rows] int32."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _heads(x, w, spec):
    """A per-head product (``w`` [nh, .., ..]) accumulated in float32."""
    return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)


def _mla_in(cfg: DecoderConfig, p, x, positions):
    """Rows to the ABSORBED attention's operands: q_abs [B, L, nh,
    kv_width] and the cached row [B, L, 1, kv_width] (rank + rope
    columns and the zero padding), in the weight type; no value, no
    gate."""
    nh, nope, rd, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                          cfg.qk_rope_head_dim, cfg.kv_lora_rank)
    wt = p["q_a"].dtype
    eps, lead = cfg.rms_norm_eps, x.shape[:-1]
    a = rms_norm(x, p["in_norm"], eps).astype(wt)
    c_q = rms_norm(_dot(a, p["q_a"]), p["q_a_norm"], eps).astype(wt)
    q = _dot(c_q, p["q_b"]).reshape(lead + (nh, nope + rd))
    kva = _dot(a, p["kv_a"])
    c = rms_norm(kva[..., :rank], p["kv_a_norm"], eps)
    k_r = rope_interleaved(kva[..., None, rank:], positions,
                           cfg.rope_theta)
    q_r = rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
    q_c = _heads(q[..., :nope].astype(wt), p["kv_b_k"], "...hn,hnr->...hr")

    def row(*parts):
        pad = cfg.kv_width - rank - rd
        if pad:
            parts += (jnp.zeros(parts[0].shape[:-1] + (pad,), jnp.float32),)
        return jnp.concatenate(parts, -1).astype(wt)
    return row(q_c, q_r), row(c[..., None, :], k_r), None, None


def _lane_of_head(cfg: DecoderConfig):
    """[nh, kv_pack] float32, one 1 a row: which part of its KV head's
    pool row query head h reads (``kv_pack`` heads lie side by side
    there, in head order)."""
    g = cfg.num_attention_heads // cfg.num_key_value_heads
    kv_head = jnp.arange(cfg.num_attention_heads) // g
    return jax.nn.one_hot(kv_head % cfg.kv_pack, cfg.kv_pack,
                          dtype=jnp.float32)


def _attn_in(cfg: DecoderConfig, rope: bool, p, x, positions):
    """Rows to the attention's operands: q [B, L, nh, hd], k, v
    [B, L, nkv, hd] and the output gate [B, L, nh * hd] (None where the
    arch has none), all in the weight type (``mla``: ``_mla_in``'s).
    Where the arch packs (``kv_pack`` n > 1) k and v come as the pool
    stores them, [B, L, nkv / n, n * hd], and q [B, L, nh, n * hd] with
    zeros outside its KV head's part, so that the product over the row
    is the product over the head."""
    if cfg.attention == "mla":
        return _mla_in(cfg, p, x, positions)
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    w = p["qkvg" if cfg.attn_gate else "qkv"]
    wt = w.dtype
    a = rms_norm(x, p["in_norm"], cfg.rms_norm_eps).astype(wt)
    cuts = [nh * hd, (nh + nkv) * hd, (nh + 2 * nkv) * hd]
    q, k, v, *g = jnp.split(_dot(a, w), cuts if cfg.attn_gate
                            else cuts[:2], axis=-1)
    lead = x.shape[:-1]
    q = rms_norm(q.reshape(lead + (nh, hd)), p["q_norm"],
                 cfg.rms_norm_eps)
    k = rms_norm(k.reshape(lead + (nkv, hd)), p["k_norm"],
                 cfg.rms_norm_eps)
    if rope:             # afmoe's full layers carry no position encoding
        q = rope_half_split(q, positions, cfg.rope_theta)
        k = rope_half_split(k, positions, cfg.rope_theta)
    v = v.reshape(lead + (nkv, hd))
    n = cfg.kv_pack
    if n > 1:
        q = (q[..., None, :] * _lane_of_head(cfg)[:, :, None]) \
            .reshape(lead + (nh, n * hd))
        k = k.reshape(lead + (nkv // n, n * hd))
        v = v.reshape(lead + (nkv // n, n * hd))
    return (q.astype(wt), k.astype(wt), v.astype(wt),
            g[0].astype(wt) if g else None)


def _attn_out(cfg: DecoderConfig, p, x, attn, gate):
    """Gate (where the arch has one) or the value's way out of the
    latent (``mla``:
    ``attn`` [B, L, nh, rank] through W_kvb^V per head), the output
    projection and the residual, with the first half of the sandwich
    where there is one: returns (h, m) with ``m = RMSNorm_pre_mlp(h)``
    in the weight type."""
    wt = p["o"].dtype
    if cfg.attention == "mla":
        o = _heads(attn, p["kv_b_v"], "...hr,hrv->...hv")
    elif cfg.kv_pack > 1:        # each head's own part of the row
        o = jnp.einsum(
            "...hnd,hn->...hd",
            attn.astype(jnp.float32).reshape(
                attn.shape[:-1] + (cfg.kv_pack, cfg.head_dim)),
            _lane_of_head(cfg))
    else:
        o = attn
    o = o.reshape(x.shape[:-1] + (-1,))
    if gate is not None:
        o = o.astype(jnp.float32) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
    o = _dot(o.astype(wt), p["o"])
    if cfg.residual == "sandwich":
        o = rms_norm(o, p["post_attn_norm"], cfg.rms_norm_eps)
    h = x + o
    return h, rms_norm(h, p["pre_mlp_norm"], cfg.rms_norm_eps).astype(wt)


def _mlp_out(cfg: DecoderConfig, p, h, f):
    if cfg.residual == "sandwich":
        f = rms_norm(f, p["post_mlp_norm"], cfg.rms_norm_eps)
    return h + f


def _dense_tail(cfg: DecoderConfig, p, x, attn, gate):
    h, m = _attn_out(cfg, p, x, attn, gate)
    return _mlp_out(cfg, p, h, swiglu(m, p["gate_up"], p["down"]))


def _conv_in(cfg: DecoderConfig, p, x):
    """Rows to the short convolution's operands: ``u = B * x`` and the
    output gate ``C`` of ``[B | C | x] = RMSNorm(h) W_in``, [B, L, d]
    each, in the weight type."""
    wt = p["conv_in"].dtype
    a = rms_norm(x, p["in_norm"], cfg.rms_norm_eps).astype(wt)
    b, c, xg = jnp.split(_dot(a, p["conv_in"]), 3, axis=-1)
    return (b * xg).astype(wt), c.astype(wt)


def _conv_out(cfg: DecoderConfig, p, x, c, y):
    """``(C * y) W_out`` and the residual: returns (h, m) as
    ``_attn_out`` does."""
    wt = p["conv_out"].dtype
    h = x + _dot((c.astype(jnp.float32) * y).astype(wt), p["conv_out"])
    return h, rms_norm(h, p["pre_mlp_norm"], cfg.rms_norm_eps).astype(wt)


def _conv_dense_tail(cfg: DecoderConfig, p, x, c, y):
    h, m = _conv_out(cfg, p, x, c, y)
    return _mlp_out(cfg, p, h, swiglu(m, p["gate_up"], p["down"]))


def _route(cfg: DecoderConfig, p, m):
    rows = m.reshape(-1, m.shape[-1])
    idx, w, _ = sigmoid_route(rows, p["router"], p["router_bias"],
                              cfg.num_experts_per_tok, cfg.route_norm,
                              cfg.route_scale, cfg.route_norm_eps)
    return idx, w


def _experts_tail(cfg: DecoderConfig, block_m: int, p, h, m, idx, w,
                  acc):
    """Shared expert on every row, this chip's routed experts on the
    rows routed to them, the second half of the sandwich, and the
    counters: ``acc`` [experts_held + 2] int32 adds the rows each held
    expert received, how many received one, and keeps the largest
    group seen."""
    rows = m.reshape(-1, m.shape[-1])
    routed, counts = dropless_experts(
        rows, idx, w, p["experts_gate_up"], p["experts_down"],
        cfg.expert_offset, block_m)
    f = routed
    if cfg.num_shared_experts:
        f = f + swiglu(rows, p["shared_gate_up"], p["shared_down"])
    acc = jnp.concatenate([
        acc[:-2] + counts,
        acc[-2:-1] + jnp.sum(counts > 0),
        jnp.maximum(acc[-1:], jnp.max(counts))]).astype(jnp.int32)
    return _mlp_out(cfg, p, h, f.reshape(h.shape)), acc


_JIT = {}


def _jitted(fn, *static):
    """One jitted program per (piece, static arguments): the cores of a
    process share them."""
    key = (fn, static)
    if key not in _JIT:
        _JIT[key] = jax.jit(functools.partial(fn, *static))
    return _JIT[key]


def decoder_block(cfg: DecoderConfig, layer: int, p: dict, x, positions,
                  view, t, *, counters=None, collector=None,
                  route_tap=None):
    """THE block: layer ``layer`` of type ``cfg.layer_types[layer]`` on
    rows ``x`` [B, L, d] float32 at ``positions`` [B, L], attending
    through ``view`` (a paged view: it appends this call's K/V, or its
    latent row, and masks by its layer's window; a ``conv`` layer mixes
    through it with the slot's stored rows instead, ``view.mix``, under
    a span ``conv`` with ``conv.project``, ``conv.mix`` and
    ``conv.out``). ``mla`` records a span
    ``mla`` a call with ``mla.project`` (down-projections, norms, RoPE,
    absorption), ``mla.attend`` (append and launch) and ``mla.out`` (on a
    dense layer the SwiGLU rides in its program); spans are opened on a
    ``collector`` only, which the step program never passes.
    ``counters`` (a dict holding ``acc``) takes the expert layer's
    device-side row counts; ``route_tap`` (a list) is handed (layer,
    view, positions, chosen experts) of every expert layer call. Returns
    the rows after the layer."""
    kind = cfg.layer_types[layer]
    if view.window != cfg.window_of(layer):
        raise ValueError(
            f"layer {layer} is {cfg.layer_types[layer]} but its cache "
            f"view's window is {view.window}: build the cache with "
            f"PagedKVCache.for_model(core, ...)")
    col = collector
    mla = col if cfg.attention == "mla" else None   # who records ``mla*``
    depth = col.span_depth if col is not None else 0
    try:
        if kind == CONV:
            if col is not None:
                col.span_begin("conv", layer=layer)
                col.span_begin("conv.project")
            u, c = _jitted(_conv_in, cfg)(p, x)
            if col is not None:
                col.span_end()
                col.span_begin("conv.mix")
            y = view.mix(u, p["conv_taps"])
            if col is not None:
                col.span_end()
                col.span_begin("conv.out")
            if not cfg.is_moe(layer):
                return _jitted(_conv_dense_tail, cfg)(p, x, c, y)
            h, m = _jitted(_conv_out, cfg)(p, x, c, y)
        else:
            if mla is not None:
                mla.span_begin("mla", layer=layer)
                mla.span_begin("mla.project")
            q, k, v, gate = _jitted(_attn_in, cfg, cfg.rotates(layer))(
                p, x, positions)
            if mla is not None:
                mla.span_end()
                mla.span_begin("mla.attend")
            attn = view.decode(Tensor(q), Tensor(k),
                               None if v is None else Tensor(v), t).data
            if mla is not None:
                mla.span_end()
                mla.span_begin("mla.out")
            if not cfg.is_moe(layer):
                return _jitted(_dense_tail, cfg)(p, x, attn, gate)
            h, m = _jitted(_attn_out, cfg)(p, x, attn, gate)
        if col is not None:
            col.span_unwind(depth)
        block_m = expert_row_block(m.shape[0] * m.shape[1],
                                   cfg.num_experts_per_tok, cfg.num_experts)
        if col is not None:
            col.span_begin("moe", layer=layer)
            col.span_begin("moe.route")
        idx, w = _jitted(_route, cfg)(p, m)
        if route_tap is not None:
            route_tap.append((layer, view, positions, idx))
        if col is not None:
            col.span_end()
            col.span_begin("moe.experts")
        out, counters["acc"] = _jitted(_experts_tail, cfg, block_m)(
            p, h, m, idx, w, counters["acc"])
    finally:
        if col is not None:
            col.span_unwind(depth)
    return out


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------

def _draw_layer(cfg: DecoderConfig, conv: bool, moe: bool, key) -> dict:
    d, nh, nkv, hd = (cfg.hidden_size, cfg.num_attention_heads,
                      cfg.num_key_value_heads, cfg.head_dim)
    wt = jnp.dtype(cfg.weight_dtype)
    keys = iter(jax.random.split(key, 16))

    def matrix(*shape):
        # fan-in scaling on the contraction axis (the one before last)
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w / math.sqrt(shape[-2])).astype(wt)

    def gain(n):                       # norm gains near 1, float32
        return 1.0 + 0.1 * jax.random.normal(next(keys), (n,),
                                             jnp.float32)

    if cfg.attention == "mla":
        nope, rd, rank, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                              cfg.kv_lora_rank, cfg.v_head_dim)
        # W_kvb is held per head and split: its key half transposed
        # (absorbed into q), its value half as it is (applied to the
        # attention's output); both have the latent as fan-in
        p = {"in_norm": gain(d), "pre_mlp_norm": gain(d),
             "q_a": matrix(d, cfg.q_lora_rank),
             "q_a_norm": gain(cfg.q_lora_rank),
             "q_b": matrix(cfg.q_lora_rank, nh * (nope + rd)),
             "kv_a": matrix(d, rank + rd), "kv_a_norm": gain(rank),
             "kv_b_k": jnp.swapaxes(matrix(nh, rank, nope), 1, 2),
             "kv_b_v": matrix(nh, rank, vd),
             "o": matrix(nh * vd, d)}
    elif conv:
        # [B | C | x] in that order; the taps as published ([d, 1, K],
        # the middle axis dropped): column K - 1 weighs the row itself
        taps = jax.random.normal(next(keys), (d, cfg.conv_L_cache),
                                 jnp.float32) / math.sqrt(cfg.conv_L_cache)
        p = {"in_norm": gain(d), "pre_mlp_norm": gain(d),
             "conv_in": matrix(d, 3 * d), "conv_taps": taps.astype(wt),
             "conv_out": matrix(d, d)}
    elif not cfg.attn_gate:      # gqa without a gate, in pre_norm
        p = {"in_norm": gain(d), "q_norm": gain(hd), "k_norm": gain(hd),
             "pre_mlp_norm": gain(d),
             "qkv": matrix(d, (nh + 2 * nkv) * hd),
             "o": matrix(nh * hd, d)}
    else:
        p = {"in_norm": gain(d), "q_norm": gain(hd), "k_norm": gain(hd),
             "post_attn_norm": gain(d), "pre_mlp_norm": gain(d),
             "post_mlp_norm": gain(d),
             "qkvg": matrix(d, (2 * nh + 2 * nkv) * hd),
             "o": matrix(nh * hd, d)}
    if not moe:
        p["gate_up"] = matrix(d, 2 * cfg.intermediate_size)
        p["down"] = matrix(cfg.intermediate_size, d)
        return p
    im = cfg.moe_intermediate_size
    p["router"] = matrix(d, cfg.num_experts)
    # small and non-zero, so that choosing with it and weighing
    # without it is exercised
    p["router_bias"] = (0.01 if cfg.use_expert_bias else 0.0) \
        * jax.random.normal(next(keys), (cfg.num_experts,), jnp.float32)
    if cfg.num_shared_experts:
        p["shared_gate_up"] = matrix(d, 2 * im * cfg.num_shared_experts)
        p["shared_down"] = matrix(im * cfg.num_shared_experts, d)
    p["experts_gate_up"] = matrix(cfg.experts_held, d, 2 * im)
    p["experts_down"] = matrix(cfg.experts_held, im, d)
    return p


def _draw_params(cfg: DecoderConfig, seed: int) -> List[dict]:
    """Every layer's parameters, drawn on the device from ``seed``."""
    keys = jax.random.split(jax.random.PRNGKey(int(seed)), cfg.num_layers)
    draw = jax.jit(_draw_layer, static_argnums=(0, 1, 2))
    return [draw(cfg, cfg.layer_types[i] == CONV, cfg.is_moe(i), keys[i])
            for i in range(cfg.num_layers)]


def _kind(caches) -> str:
    """The expert counters a call books under: ``decode`` (one row a
    slot) or ``mixed`` (a packed step or a prompt chunk)."""
    return "decode" if isinstance(caches[0], PagedLayerCache) else "mixed"


class DecoderCore:
    """The decoder stack as the engines drive it. Not an ``nn.Layer``:
    its parameters are plain device arrays in per-layer dicts
    (``self.params``), drawn from ``seed`` by ``jax.random``. Called, it
    runs per op (the specification, and CPU tier-1's path); it also
    offers its forward PURE (``step_operands`` / ``step_forward`` /
    ``step_rebind``), which ``paged_cache.model_call`` runs as one step
    program on the kernel path."""

    # take the packed mixed step whenever the scheduler may: the block
    # needs every row's position, which the ragged layout carries
    prefers_packed_step = True

    def __init__(self, config: DecoderConfig, seed: int = 0):
        self.config = cfg = config
        self.embed_dim = cfg.hidden_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads // cfg.kv_pack
        self.head_dim = cfg.kv_width
        # what ``PagedKVCache.for_model`` reads: ``mla`` caches one
        # latent row a position, whose leading columns are the value;
        # packed heads (``kv_pack``) attend at the head's scale, not
        # the stored row's
        self.latent_cache = {"v_dim": cfg.kv_lora_rank,
                             "sm_scale": cfg.attn_scale} \
            if cfg.attention == "mla" else \
            {"sm_scale": cfg.attn_scale} if cfg.kv_pack > 1 else None
        # a conv layer holds no K/V: its last kernel - 1 input rows a
        # slot live in the cache's state store
        self.layer_state = tuple(
            (cfg.conv_L_cache - 1, cfg.hidden_size) if t == CONV else None
            for t in cfg.layer_types) \
            if CONV in cfg.layer_types else None
        self.num_layers = cfg.num_layers
        self.layer_windows = tuple(cfg.window_of(i)
                                   for i in range(cfg.num_layers))
        self.params: List[dict] = _draw_params(cfg, seed)
        self.collector = None      # PagedServingEngine keeps it current
        # a list here receives every expert layer call's (layer, view,
        # positions, chosen experts): the benchmark's probe compares
        # routing at near-ties with it; None (the default) costs nothing
        self.route_tap: Optional[list] = None
        # expert-layer counters, device side, by the kind of call:
        # "decode" (one row a slot) and "mixed" (a packed step or a
        # prompt chunk); [layer] -> {"acc": int32 [experts_held + 2]}
        self._moe_layers = [i for i in range(cfg.num_layers)
                            if cfg.is_moe(i)]
        self._counters = {
            kind: {i: {"acc": jnp.zeros((cfg.experts_held + 2,),
                                        jnp.int32)}
                   for i in self._moe_layers}
            for kind in ("decode", "mixed")}
        self._calls = {"decode": 0, "mixed": 0}
        self._rows = {"decode": 0, "mixed": 0}

    # -- the protocol -------------------------------------------------
    def __call__(self, src, attn_mask=None, caches=None, time_step=None,
                 **kwargs):
        x, t, positions = self._inputs(src, caches, time_step)
        kind = _kind(caches)
        self._count(kind, x)
        for i in range(self.num_layers):
            x = decoder_block(
                self.config, i, self.params[i], x, positions, caches[i],
                t, counters=self._counters[kind].get(i),
                collector=self.collector, route_tap=self.route_tap)
        return Tensor(x), list(caches)

    @staticmethod
    def _inputs(src, caches, time_step):
        """(rows [B, L, d] float32, start positions t [B], every row's
        position [B, L]) of a call."""
        if caches is None or time_step is None or \
                not getattr(caches[0], "is_paged", False):
            raise ValueError("DecoderCore serves through paged cache "
                             "views (caches=, time_step=) only")
        x = jnp.asarray(src.data if isinstance(src, Tensor) else src,
                        jnp.float32)
        b, l = x.shape[0], x.shape[1]
        t = time_step.data if isinstance(time_step, Tensor) \
            else jnp.asarray(time_step, jnp.int32)
        t = jnp.broadcast_to(t.reshape(-1).astype(jnp.int32), (b,))
        return x, t, caches[0].positions(t, l)

    def _count(self, kind: str, x) -> None:
        if self._moe_layers:
            self._calls[kind] += 1
            self._rows[kind] += int(x.shape[0]) * int(x.shape[1])

    # -- the step program (``layer_jit``'s pure-forward protocol) -----
    # The same blocks, traced whole by ``paged_cache.model_call``: the
    # weights are plain operands, the expert counters of the call's kind
    # donated ones, and what the per-op call does on the host (the
    # ``moe`` / ``mla`` / ``conv`` spans, ``_calls`` / ``_rows``, the
    # routing tap) is either left out or done by ``step_rebind``.
    def step_operands(self, src, caches, time_step):
        counters = self._counters[_kind(caches)]
        return {i: c["acc"] for i, c in counters.items()}, self.params

    def step_forward(self, acc, params, src, caches, time_step):
        """The forward with no host effect: returns ((rows, positions,
        each expert layer's chosen experts), the counters)."""
        x, t, positions = self._inputs(src, caches, time_step)
        counters = {i: {"acc": a} for i, a in acc.items()}
        tap = []
        for i in range(self.num_layers):
            x = decoder_block(self.config, i, params[i], x, positions,
                              caches[i], t, counters=counters.get(i),
                              route_tap=tap)
        return ((x, positions, tuple(entry[3] for entry in tap)),
                {i: c["acc"] for i, c in counters.items()})

    def step_rebind(self, acc, result, src, caches, time_step):
        kind = _kind(caches)
        for i, a in acc.items():
            self._counters[kind][i]["acc"] = a
        self._count(kind, src)
        x, positions, chosen = result
        if self.route_tap is not None:
            self.route_tap.extend(
                (i, caches[i], positions, idx)
                for i, idx in zip(self._moe_layers, chosen))
        return Tensor(x), list(caches)

    # -- accounting ---------------------------------------------------
    def weight_bytes(self) -> int:
        """Bytes of every parameter as stored."""
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for p in self.params for a in p.values())

    def moe_metrics(self) -> dict:
        """Cold scrape (``MetricsRegistry.attach("moe", ...)`` and the
        benchmark): pulls the device-side counters to the host. By kind
        of call, summed over the expert layers: ``calls`` (model
        calls), ``rows`` (rows they carried), ``layer_calls``,
        ``rows_routed_here`` (assignments that fell on a held expert),
        ``experts_hit`` (held experts that received a row, summed over
        layer calls), ``rows_per_expert_mean`` (a held expert, a layer
        call) and ``rows_per_expert_max`` (the largest group of any
        call)."""
        held = self.config.experts_held
        out = {"experts": self.config.num_experts, "experts_held": held,
               "expert_offset": self.config.expert_offset,
               "top_k": self.config.num_experts_per_tok}
        for kind, layers in self._counters.items():
            acc = np.zeros((held + 2,), np.int64)
            for c in layers.values():
                a = np.asarray(c["acc"]).astype(np.int64)
                acc[:-1] += a[:-1]
                acc[-1] = max(acc[-1], a[-1])
            layer_calls = self._calls[kind] * len(layers)
            routed = int(acc[:held].sum())
            out[kind] = {
                "calls": self._calls[kind], "rows": self._rows[kind],
                "layer_calls": layer_calls,
                "rows_routed_here": routed,
                "experts_hit": int(acc[held]),
                "rows_per_expert_mean":
                    routed / (layer_calls * held) if layer_calls else 0.0,
                "rows_per_expert_max": int(acc[held + 1]),
                "load": {str(e): int(acc[e]) for e in range(held)},
            }
        return out
