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

What the block covers today is the ``afmoe`` layer (Arcee Trinity):

    a = RMSNorm_in(h);  q, k, v, g = a Wq, a Wk, a Wv, a Wg
    q, k = RMSNorm_q(q), RMSNorm_k(k)            (over the head dim)
    sliding layers only: RoPE (half-split) on q, k; window W
    o = (softmax(q k^T / sqrt(hd), causal [and window]) v * sigmoid(g)) Wo
    h = h + RMSNorm_post_attn(o)
    m = RMSNorm_pre_mlp(h);  h = h + RMSNorm_post_mlp(F(m))

with ``F`` a SwiGLU in the first ``num_dense_layers`` layers and, after
them, a shared SwiGLU expert plus this chip's share of the routed ones
(``inference/moe_serving.py``: sigmoid scores over ALL experts, top-k of
score + bias, dropless grouped GEMM over the experts held here). Each KV
head serves ``num_attention_heads / num_key_value_heads`` query heads;
the pool stores the KV heads only.

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
           "rope_half_split"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The catalog's ``config.json`` keys, plus which experts live here
    (``experts_held`` of ``num_experts`` from ``expert_offset``) and the
    stored weight type."""
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]
    sliding_window: int
    num_dense_layers: int
    intermediate_size: int
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

    KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "layer_types", "sliding_window",
            "num_dense_layers", "intermediate_size", "num_experts",
            "num_experts_per_tok", "num_shared_experts",
            "moe_intermediate_size", "route_norm", "route_scale",
            "rope_theta", "rms_norm_eps", "mup_enabled", "experts_held",
            "expert_offset", "weight_dtype")

    @classmethod
    def from_spec(cls, spec: dict) -> "DecoderConfig":
        kw = {k: spec[k] for k in cls.KEYS if spec.get(k) is not None}
        kw["layer_types"] = tuple(kw["layer_types"])
        cfg = cls(**kw)
        held = cfg.num_experts if cfg.experts_held is None \
            else cfg.experts_held
        cfg = dataclasses.replace(cfg, experts_held=int(held))
        bad = set(cfg.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if cfg.head_dim % 2:
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


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _attn_in(cfg: DecoderConfig, sliding: bool, p, x, positions):
    """Rows to the attention's operands: q [B, L, nh, hd], k, v
    [B, L, nkv, hd] and the output gate [B, L, nh * hd], all in the
    weight type."""
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    wt = p["qkvg"].dtype
    a = rms_norm(x, p["in_norm"], cfg.rms_norm_eps).astype(wt)
    qkvg = _dot(a, p["qkvg"])
    q, k, v, g = jnp.split(
        qkvg, [nh * hd, (nh + nkv) * hd, (nh + 2 * nkv) * hd], axis=-1)
    lead = x.shape[:-1]
    q = rms_norm(q.reshape(lead + (nh, hd)), p["q_norm"],
                 cfg.rms_norm_eps)
    k = rms_norm(k.reshape(lead + (nkv, hd)), p["k_norm"],
                 cfg.rms_norm_eps)
    if sliding:                 # full layers carry no position encoding
        q = rope_half_split(q, positions, cfg.rope_theta)
        k = rope_half_split(k, positions, cfg.rope_theta)
    return (q.astype(wt), k.astype(wt),
            v.reshape(lead + (nkv, hd)).astype(wt), g.astype(wt))


def _attn_out(cfg: DecoderConfig, p, x, attn, gate):
    """Gate, output projection and the first half of the sandwich:
    returns (h, m) with ``m = RMSNorm_pre_mlp(h)`` in the weight
    type."""
    wt = p["o"].dtype
    o = attn.reshape(gate.shape).astype(jnp.float32) \
        * jax.nn.sigmoid(gate.astype(jnp.float32))
    h = x + rms_norm(_dot(o.astype(wt), p["o"]), p["post_attn_norm"],
                     cfg.rms_norm_eps)
    return h, rms_norm(h, p["pre_mlp_norm"], cfg.rms_norm_eps).astype(wt)


def _mlp_out(cfg: DecoderConfig, p, h, f):
    return h + rms_norm(f, p["post_mlp_norm"], cfg.rms_norm_eps)


def _dense_tail(cfg: DecoderConfig, p, x, attn, gate):
    h, m = _attn_out(cfg, p, x, attn, gate)
    return _mlp_out(cfg, p, h, swiglu(m, p["gate_up"], p["down"]))


def _route(cfg: DecoderConfig, p, m):
    rows = m.reshape(-1, m.shape[-1])
    idx, w, _ = sigmoid_route(rows, p["router"], p["router_bias"],
                              cfg.num_experts_per_tok, cfg.route_norm,
                              cfg.route_scale)
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
    through ``view`` (a paged view: it appends this call's K/V and
    masks by its layer's window). ``counters`` (a dict holding ``acc``)
    takes the expert layer's device-side row counts; ``route_tap`` (a
    list) is handed (layer, view, positions, chosen experts) of every
    expert layer call, the arrays on the device. Returns the rows after
    the layer."""
    sliding = cfg.layer_types[layer] == SLIDING
    if view.window != cfg.window_of(layer):
        raise ValueError(
            f"layer {layer} is {cfg.layer_types[layer]} but its cache "
            f"view's window is {view.window}: build the cache with "
            f"PagedKVCache.for_model(core, ...)")
    q, k, v, gate = _jitted(_attn_in, cfg, sliding)(p, x, positions)
    attn = view.decode(Tensor(q), Tensor(k), Tensor(v), t).data
    if not cfg.is_moe(layer):
        return _jitted(_dense_tail, cfg)(p, x, attn, gate)
    h, m = _jitted(_attn_out, cfg)(p, x, attn, gate)
    block_m = expert_row_block(m.shape[0] * m.shape[1],
                               cfg.num_experts_per_tok, cfg.num_experts)
    col = collector
    depth = col.span_depth if col is not None else 0
    try:
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

def _draw_layer(cfg: DecoderConfig, moe: bool, key) -> dict:
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
    p["router_bias"] = 0.01 * jax.random.normal(
        next(keys), (cfg.num_experts,), jnp.float32)
    if cfg.num_shared_experts:
        p["shared_gate_up"] = matrix(d, 2 * im * cfg.num_shared_experts)
        p["shared_down"] = matrix(im * cfg.num_shared_experts, d)
    p["experts_gate_up"] = matrix(cfg.experts_held, d, 2 * im)
    p["experts_down"] = matrix(cfg.experts_held, im, d)
    return p


class DecoderCore:
    """The decoder stack as the engines drive it. Not an ``nn.Layer``:
    its parameters are plain device arrays in per-layer dicts
    (``self.params``), drawn from ``seed`` by ``jax.random``."""

    # take the packed mixed step whenever the scheduler may: the block
    # needs every row's position, which the ragged layout carries
    prefers_packed_step = True

    def __init__(self, config: DecoderConfig, seed: int = 0):
        self.config = cfg = config
        self.embed_dim = cfg.hidden_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.num_layers = cfg.num_layers
        self.layer_windows = tuple(cfg.window_of(i)
                                   for i in range(cfg.num_layers))
        keys = jax.random.split(jax.random.PRNGKey(int(seed)),
                                cfg.num_layers)
        draw = jax.jit(_draw_layer, static_argnums=(0, 1))
        self.params: List[dict] = [draw(cfg, cfg.is_moe(i), keys[i])
                                   for i in range(cfg.num_layers)]
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
        positions = caches[0].positions(t, l)
        kind = "decode" if isinstance(caches[0], PagedLayerCache) \
            else "mixed"
        if self._moe_layers:
            self._calls[kind] += 1
            self._rows[kind] += b * l
        for i in range(self.num_layers):
            x = decoder_block(
                self.config, i, self.params[i], x, positions, caches[i],
                t, counters=self._counters[kind].get(i),
                collector=self.collector, route_tap=self.route_tap)
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
