"""Fused transformer layers (ref: /root/reference/python/paddle/incubate/nn/
layer/fused_transformer.py — FusedMultiTransformer:1021 with
cache_kvs/time_step decode path; CUDA impl
fused_multi_transformer_op.cu.h:138 (attention), :420 (ffn), :835
(cache-KV decode)).

The reference fuses qkv+rotary+cacheKV+attention+residual+LN into one CUDA
kernel chain; here each block is a single jnp expression chain — XLA fuses
the elementwise segments into the GEMMs, and decode-time cache append is a
dynamic_update_slice into a preallocated [B, max_len, H, D] cache (static
shapes, MXU-friendly)."""
from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...framework import device
from ...framework.op import apply
from ...framework.tensor import Tensor
from ... import nn
from ...nn import functional as F


class FusedMultiHeadAttention(nn.Layer):
    """ref: fused_transformer.py FusedMultiHeadAttention — pre/post LN +
    qkv proj + attention + out proj + residual in one call."""

    def __init__(self, embed_dim, num_heads, dropout_rate=0.5,
                 attn_dropout_rate=0.5, kdim=None, vdim=None,
                 normalize_before=False, need_weights=False, qkv_weight_attr=None,
                 qkv_bias_attr=None, linear_weight_attr=None,
                 linear_bias_attr=None, pre_ln_scale_attr=None,
                 pre_ln_bias_attr=None, ln_scale_attr=None, ln_bias_attr=None,
                 epsilon=1e-5, nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.normalize_before = normalize_before
        self.dropout_rate = dropout_rate
        self.qkv = nn.Linear(embed_dim, 3 * embed_dim)
        self.out_proj = nn.Linear(embed_dim, embed_dim)
        self.norm = nn.LayerNorm(embed_dim, epsilon)
        self.dropout = nn.Dropout(dropout_rate)

    def forward(self, query, key=None, value=None, attn_mask=None,
                cache=None):
        from ...ops.manipulation import reshape, split
        residual = query
        x = self.norm(query) if self.normalize_before else query
        b, l = x.shape[0], x.shape[1]
        q, k, v = split(self.qkv(x), 3, axis=-1)
        q = reshape(q, [b, l, self.num_heads, self.head_dim])
        k = reshape(k, [b, l, self.num_heads, self.head_dim])
        v = reshape(v, [b, l, self.num_heads, self.head_dim])
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask)
        out = self.out_proj(reshape(out, [b, l, self.embed_dim]))
        out = residual + self.dropout(out)
        if not self.normalize_before:
            out = self.norm(out)
        return out


class FusedFeedForward(nn.Layer):
    def __init__(self, d_model, dim_feedforward, dropout_rate=0.1,
                 epsilon=1e-05, activation="relu", act_dropout_rate=None,
                 normalize_before=False, linear1_weight_attr=None,
                 linear1_bias_attr=None, linear2_weight_attr=None,
                 linear2_bias_attr=None, ln1_scale_attr=None,
                 ln1_bias_attr=None, ln2_scale_attr=None, ln2_bias_attr=None,
                 nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.normalize_before = normalize_before
        self.fc1 = nn.Linear(d_model, dim_feedforward)
        self.fc2 = nn.Linear(dim_feedforward, d_model)
        self.norm = nn.LayerNorm(d_model, epsilon)
        self.dropout = nn.Dropout(dropout_rate)
        self.act = getattr(F, activation)

    def forward(self, src, cache=None):
        residual = src
        x = self.norm(src) if self.normalize_before else src
        x = self.fc2(self.dropout(self.act(self.fc1(x))))
        x = residual + self.dropout(x)
        if not self.normalize_before:
            x = self.norm(x)
        return x


class FusedTransformerEncoderLayer(nn.Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout_rate=0.1,
                 activation="relu", attn_dropout_rate=None,
                 act_dropout_rate=None, normalize_before=False, **kw):
        super().__init__()
        self.fused_attn = FusedMultiHeadAttention(
            d_model, nhead, dropout_rate,
            attn_dropout_rate or dropout_rate,
            normalize_before=normalize_before)
        self.ffn = FusedFeedForward(d_model, dim_feedforward, dropout_rate,
                                    activation=activation,
                                    normalize_before=normalize_before)

    def forward(self, src, src_mask=None, cache=None):
        out = self.fused_attn(src, attn_mask=src_mask)
        return self.ffn(out)


class FusedMultiTransformer(nn.Layer):
    """Decoder stack with preallocated KV caches + time_step decode
    (ref: fused_transformer.py:1021). cache_kvs: per-layer
    [2, B, H, max_len, D] like the reference; time_step selects decode
    branch (single-token append via dynamic_update_slice)."""

    def __init__(self, embed_dim, num_heads, dim_feedforward,
                 dropout_rate=0.0, activation="gelu", normalize_before=True,
                 ln_scale_attrs=None, ln_bias_attrs=None,
                 qkv_weight_attrs=None, qkv_bias_attrs=None,
                 linear_weight_attrs=None, linear_bias_attrs=None,
                 ffn_ln_scale_attrs=None, ffn_ln_bias_attrs=None,
                 ffn1_weight_attrs=None, ffn1_bias_attrs=None,
                 ffn2_weight_attrs=None, ffn2_bias_attrs=None,
                 epsilon=1e-5, num_layers=-1, nranks=1, trans_qkvw=True,
                 ring_id=-1, name=None):
        super().__init__()
        if num_layers == -1:
            num_layers = len(qkv_weight_attrs) if qkv_weight_attrs else 1
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.num_layers = num_layers
        self.normalize_before = normalize_before
        self.layers = nn.LayerList()
        for _ in range(num_layers):
            blk = nn.Layer()
            blk.ln = nn.LayerNorm(embed_dim, epsilon)
            blk.qkv = nn.Linear(embed_dim, 3 * embed_dim)
            blk.out_proj = nn.Linear(embed_dim, embed_dim)
            blk.ffn_ln = nn.LayerNorm(embed_dim, epsilon)
            blk.ffn1 = nn.Linear(embed_dim, dim_feedforward)
            blk.ffn2 = nn.Linear(dim_feedforward, embed_dim)
            self.layers.append(blk)
        self._act_name = activation
        self.activation = getattr(F, activation)

    def gen_cache(self, batch, max_len, dtype="float32"):
        import paddle_tpu as paddle
        # round the cache length up to a lane multiple: the flash-decode
        # kernel blocks the cache axis in 128-wide steps, and a max_len
        # like 200 would otherwise force an 8-wide block (16x more grid
        # steps for the same bytes)
        if max_len > 128:
            max_len = -(-max_len // 128) * 128
        return [paddle.zeros([2, batch, self.num_heads, max_len,
                              self.head_dim], dtype=dtype)
                for _ in range(self.num_layers)]

    def gen_paged_cache(self, block_size, num_blocks, max_seqs,
                        max_blocks_per_seq=None, dtype="float32",
                        prefix_cache=False):
        """Block-paged alternative to gen_cache: returns a PagedKVCache
        whose ``.views`` list rides in the same ``caches=`` argument —
        the cache layout is a protocol, not a tensor shape (see
        inference/paged_cache.py). ``prefix_cache`` turns on the
        cross-request chained-hash block index + cached-free tier."""
        from ...inference.paged_cache import PagedKVCache
        return PagedKVCache.for_model(
            self, block_size, num_blocks, max_seqs,
            max_blocks_per_seq=max_blocks_per_seq, dtype=dtype,
            prefix_cache=prefix_cache)

    def _proj(self, i, blk, name, x):
        """Linear-projection hook; the int8 subclass overrides this."""
        return getattr(blk, name)(x)

    def _ffn_block(self, i, blk, x):
        """Post-attention FFN sub-block (residual + LN wrapping included).

        Overridable seam: the attention/cache schedule in forward() is
        shared by every serving mode, so a subclass that only changes the
        FFN (e.g. inference.moe_serving.MoeServingCore's routed expert
        FFN) inherits all paged/prefix/speculative cache behavior."""
        residual = x
        h = blk.ffn_ln(x) if self.normalize_before else x
        h = self._proj(i, blk, "ffn2", self.activation(
            self._proj(i, blk, "ffn1", h)))
        x = residual + h
        if not self.normalize_before:
            x = blk.ffn_ln(x)
        return x

    def forward(self, src, attn_mask=None, caches=None, time_step=None,
                **kwargs):
        from ...ops.manipulation import reshape, split, transpose
        x = src
        b, l = x.shape[0], x.shape[1]
        new_caches = [] if caches is not None else None
        for i, blk in enumerate(self.layers):
            residual = x
            h = blk.ln(x) if self.normalize_before else x
            q, k, v = split(self._proj(i, blk, "qkv", h), 3, axis=-1)
            q = reshape(q, [b, l, self.num_heads, self.head_dim])
            k = reshape(k, [b, l, self.num_heads, self.head_dim])
            v = reshape(v, [b, l, self.num_heads, self.head_dim])
            if caches is not None and time_step is not None and \
                    getattr(caches[i], "is_paged", False):
                # paged-cache protocol (inference/paged_cache.py): the
                # per-layer view appends k/v through its block table
                # and attends over the sequence's pages — Pallas paged
                # kernel on TPU, jnp gather + the same masked-sdpa
                # codepath as the dense ragged branch on CPU (so paged
                # and dense decode stay bit-identical there). l == 1
                # is the plain decode step; l > 1 appends l tokens per
                # row from time_step on and scores each causally (the
                # speculative-decode verification step). Prompt
                # PREFILL rides the same protocol through
                # PagedKVCache.prefill_views: batch-1 chunk calls
                # whose per-layer PagedPrefillView appends the chunk
                # straight into the slot's pages and attends with a
                # multi-row masked sdpa (inference/scheduler.py
                # chunked_prefill) — no dense scratch. All paths
                # assume the block tables already cover [t, t+l).
                t = time_step.data if isinstance(time_step, Tensor) \
                    else jnp.asarray(time_step, jnp.int32)
                # per-row positions like the ragged dense path; a
                # scalar/shape-[1] time_step broadcasts across rows
                t = jnp.broadcast_to(t.reshape(-1).astype(jnp.int32),
                                     (b,))
                attn = caches[i].decode(q, k, v, t)
                new_caches.append(caches[i])
            elif caches is not None and time_step is not None:
                # decode: append k/v at time_step into the static cache.
                # time_step stays a TRACED scalar (dynamic_update_slice,
                # the decode-kernel lens, and the mask below all accept
                # traced indices) — no host sync, no per-step retrace,
                # and forward can sit under jit with a traced time_step.
                cache = caches[i]
                # python-int time_step keeps a static fast path (slice
                # instead of full-cache mask); Tensor/traced time_step
                # stays traced — no host sync, no per-step retrace
                t_static = int(time_step) if isinstance(
                    time_step, (int, np.integer)) else None
                t = time_step.data if isinstance(time_step, Tensor) \
                    else jnp.asarray(time_step, jnp.int32)
                # ragged = per-row positions, a [batch] vector
                # (continuous-batching serving, every slot at its own
                # cache offset — ref masked-mha per-batch lens,
                # fused_multi_transformer_op.cu.h:835). The reference
                # API's documented shape-[1] time_step stays a SCALAR
                # (b==1 per-row is equivalent anyway).
                ragged = t.ndim == 1 and b > 1 and t.shape[0] == b
                if not ragged:
                    t = t.reshape(())

                if ragged:
                    def upd(c, ka, va, tv):
                        def row(cs, ks, vs, tb):  # cs [2, H, S, D]
                            kc = jax.lax.dynamic_update_slice(
                                cs[0], ks, (0, tb, 0))
                            vc = jax.lax.dynamic_update_slice(
                                cs[1], vs, (0, tb, 0))
                            return jnp.stack([kc, vc])
                        return jax.vmap(row, in_axes=(1, 0, 0, 0),
                                        out_axes=1)(
                            c, jnp.moveaxis(ka, 1, 2),
                            jnp.moveaxis(va, 1, 2), tv)
                    cache = apply(upd, (cache, k, v, Tensor(t)),
                                  op_name="cache_kv")
                else:
                    def upd(c, ka, va):
                        kc = jax.lax.dynamic_update_slice(
                            c[0], jnp.moveaxis(ka, 1, 2), (0, 0, t, 0))
                        vc = jax.lax.dynamic_update_slice(
                            c[1], jnp.moveaxis(va, 1, 2), (0, 0, t, 0))
                        return jnp.stack([kc, vc])
                    cache = apply(upd, (cache, k, v), op_name="cache_kv")
                new_caches.append(cache)
                if l == 1 and device.use_pallas_kernels():
                    # flash-decoding over the static cache (ref
                    # fused_multi_transformer_op.cu.h:835 masked mha)
                    from ...ops.pallas.decode_attention import \
                        decode_attention

                    if ragged:
                        # t rides as an ARGUMENT: a traced closure cell
                        # would bust the per-op executable cache
                        def dec_r(c, q_, tv):
                            kc = jnp.swapaxes(c[0], 1, 2)  # [B,S,H,D]
                            vc = jnp.swapaxes(c[1], 1, 2)
                            return decode_attention(q_[:, 0], kc, vc,
                                                    tv + 1)[:, None]
                        attn = apply(dec_r, (cache, q, Tensor(t)),
                                     op_name="decode_attention")
                    else:
                        def dec(c, q_):
                            kc = jnp.swapaxes(c[0], 1, 2)
                            vc = jnp.swapaxes(c[1], 1, 2)
                            lens = jnp.zeros((q_.shape[0],), jnp.int32) \
                                + (t + 1)
                            return decode_attention(q_[:, 0], kc, vc,
                                                    lens)[:, None]
                        attn = apply(dec, (cache, q),
                                     op_name="decode_attention")
                elif t_static is not None:
                    # static t: slice just the valid prefix (much
                    # cheaper than attending over max_len when t << S)
                    ts = t_static
                    k_full = transpose(cache[0], [0, 2, 1, 3])[:, :ts + l]
                    v_full = transpose(cache[1], [0, 2, 1, 3])[:, :ts + l]
                    mask = None
                    if l > 1:
                        qpos = ts + jnp.arange(l)[:, None]
                        kpos = jnp.arange(ts + l)[None, :]
                        mask = Tensor(jnp.where(kpos <= qpos, 0.0, -1e30)
                                      .astype(jnp.float32))
                    attn = F.scaled_dot_product_attention(
                        q, k_full, v_full, attn_mask=mask)
                else:
                    # traced t: attend over the FULL static cache with a
                    # validity mask (a [:t+l] slice would need static
                    # t): query i sees cache pos <= t+i. Ragged t ([B])
                    # builds a per-row mask [B, 1, l, S].
                    S = cache.shape[3]
                    k_full = transpose(cache[0], [0, 2, 1, 3])
                    v_full = transpose(cache[1], [0, 2, 1, 3])
                    if ragged:
                        qpos = (t[:, None, None, None]
                                + jnp.arange(l)[None, None, :, None])
                        kpos = jnp.arange(S)[None, None, None, :]
                    else:
                        qpos = t + jnp.arange(l)[:, None]
                        kpos = jnp.arange(S)[None, :]
                    mask = Tensor(jnp.where(kpos <= qpos, 0.0, -1e30)
                                  .astype(jnp.float32))
                    attn = F.scaled_dot_product_attention(
                        q, k_full, v_full, attn_mask=mask)
            else:
                attn = F.scaled_dot_product_attention(
                    q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None)
                if caches is not None:
                    new_caches.append(caches[i])
            attn = self._proj(i, blk, "out_proj",
                              reshape(attn, [b, l, self.embed_dim]))
            x = residual + attn
            if not self.normalize_before:
                x = blk.ln(x)
            x = self._ffn_block(i, blk, x)
        if caches is not None:
            return x, new_caches
        return x

class FusedMultiTransformerInt8(FusedMultiTransformer):
    """Int8 weight-quantized decoder stack (ref: fused_multi_transformer
    _int8 op, /root/reference/paddle/fluid/operators/fused/
    fused_multi_transformer_int8_op.cu + attn_gemm_int8.h's cublasLt int8
    GEMMs — here the MXU int8 path via quantization.quantized_matmul).

    Construct with float weights (same signature as FusedMultiTransformer)
    then call `quantize_weights()` — per-out-channel abs-max int8 — or
    build from a trained FusedMultiTransformer with `from_float(model)`.
    Activations stay bf16/fp32 (weight-only), the dominant TPU serving
    mode. The forward schedule is inherited; only the linear projections
    (_proj) change."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._quantized = False

    def quantize_weights(self, bits=8):
        """Snapshot int8 weights and DROP the float linear weights:
        quantization freezes the weights at this point (later float-side
        mutation cannot silently desync from the int8 copies, and the
        float tensors stop double-counting in parameters()). The int8
        weights + scales are registered as persistable BUFFERS on each
        linear, so state_dict()/set_state_dict round-trip the quantized
        model (construct + quantize_weights() first, then load)."""
        import jax.numpy as _jnp
        from ...quantization.functional import quantize as _quantize
        if self._quantized:
            raise RuntimeError(
                "already quantized: the float weights were dropped at "
                "quantize time. To re-quantize at a different bit width, "
                "rebuild via FusedMultiTransformerInt8.from_float(model, "
                "bits=...) from the float model.")
        self._bits = bits
        self._int8 = []
        for blk in self.layers:
            entry = {}
            for name in ("qkv", "out_proj", "ffn1", "ffn2"):
                lin = getattr(blk, name)
                w = lin.weight.data
                # all-zero channels would give scale 0 -> NaN int8
                scale = _jnp.maximum(_jnp.max(_jnp.abs(w), axis=0), 1e-8)
                wq = _quantize(lin.weight, scale, bits=bits, axis=-1)
                wq = wq if isinstance(wq, Tensor) else Tensor(wq)
                scale_t = Tensor(scale)
                lin.weight = None  # Layer.__setattr__ drops the param
                lin.register_buffer("weight_int8", wq)
                lin.register_buffer("weight_scale", scale_t)
                # entry aliases the SAME Tensor objects as the buffers:
                # set_state_dict mutates them in place (set_value), so a
                # reloaded checkpoint reaches _proj without re-wiring
                entry[name] = (wq, scale_t, lin.bias)
            self._int8.append(entry)
        self._quantized = True
        return self

    @classmethod
    def from_float(cls, model: "FusedMultiTransformer", bits: int = 8):
        m = cls(model.embed_dim, model.num_heads,
                model.layers[0].ffn1.weight.shape[1],
                activation=model._act_name,
                num_layers=model.num_layers,
                normalize_before=model.normalize_before,
                epsilon=model.layers[0].ln._epsilon)
        # copy the float model's values into m's OWN Parameter objects
        # (jnp arrays are immutable, so sharing the array data is safe;
        # sharing the modules by reference is not — the source model
        # would see its weights dropped by quantize_weights, and later
        # source-side updates would silently desync from the int8 copies)
        for dst, srcb in zip(m.layers, model.layers):
            for name in ("ln", "qkv", "out_proj", "ffn_ln", "ffn1",
                         "ffn2"):
                dmod, smod = getattr(dst, name), getattr(srcb, name)
                for pname, p in smod._parameters.items():
                    if p is not None and \
                            dmod._parameters.get(pname) is not None:
                        dmod._parameters[pname]._data = p.data
        return m.quantize_weights(bits=bits)

    def _proj(self, i, blk, name, x):
        if not self._quantized:
            raise RuntimeError("call quantize_weights() (or from_float) "
                               "before forward")
        import jax.numpy as jnp
        from ...quantization.functional import quantized_matmul
        wq, scale, bias = self._int8[i][name]
        # dequantize with the SAME bit width used at quantize time; the
        # activation dtype (bf16 in serving) flows through unchanged
        out = quantized_matmul(x, wq, scale, bits=self._bits,
                               out_dtype=jnp.dtype(str(x.dtype)))
        return out + bias if bias is not None else out
