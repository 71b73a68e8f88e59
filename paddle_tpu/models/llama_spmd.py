"""Flagship SPMD Llama trainer — the pod-scale performance path.

The reference trains this model class through Fleet hybrid parallel:
per-rank processes, NCCL groups per axis, 1F1B p2p, ZeRO state partitioning
(SURVEY.md §2.4). Here the whole hybrid step is ONE jitted program over the
global mesh:

- dp:        batch dim sharded over 'dp'
- mp (TP):   Megatron column/row sharding on qkv/o and gate/up/down + vocab
             — GSPMD inserts the allreduces
- pp:        decoder stack split into stages, stacked on a 'pp'-sharded
             leading dim, scheduled by the shard_map ppermute pipeline
             (parallel/pipeline.py); backward = AD through the schedule
- sep (SP):  activations and K/V stay sequence-sharded end to end; attention
             is blockwise ring attention with K/V ppermuted around the sep
             ring (parallel/ring_attention.py) — no full K/V gather
- ZeRO:      AdamW moments + fp32 master weights sharded over 'sharding'
- bf16 compute, fp32 master accumulate; per-block jax.checkpoint (remat)

The dygraph/user-facing Llama lives in models/llama.py; this trainer is the
analog of the reference's fused static path (fused_multi_transformer +
distributed_strategy), built TPU-first.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework.device import on_tpu
from ..parallel import mesh as mesh_mod
from ..parallel.pipeline import spmd_pipeline
from .llama import LlamaConfig


def _place(a, *spec):
    return mesh_mod.shard_tensor_data(a, P(*spec))


def _flash_per_shard(q, k, v, scale):
    """Causal flash attention on [B, T, nh, hd] under whatever mesh the
    caller runs on. Mosaic kernels are not partitioned automatically:
    on more than one device the lowering demands that EVERY mesh axis —
    size-1 axes included — be manual around the call ("wrap the call in
    a shard_map"). So the kernel runs per shard inside a shard_map over
    all axes the caller has not already made manual, batch split over
    'dp' and heads over 'mp' where GSPMD shards them; attention is
    independent per batch row and per head, so no collective is needed.
    Nests inside the pipeline's own shard_map (manual on 'pp'/'sep')."""
    from ..ops.pallas.flash_attention import flash_attention_blhd
    fn = functools.partial(flash_attention_blhd, causal=True,
                           sm_scale=scale)
    m = mesh_mod._current_mesh()
    if math.prod(m.shape.values()) == 1:
        return fn(q, k, v)
    axes = set(m.axis_names) - mesh_mod._manual_axes(m)

    def over(a):
        return a if a in axes and m.shape[a] > 1 else None
    spec = P(over("dp"), None, over("mp"), None)
    return jax.shard_map(fn, mesh=m, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=axes,
                         check_vma=False)(q, k, v)


def _zero_spec(shape, base_spec, axis="sharding"):
    """Add 'sharding' to the first free, divisible dim of base_spec."""
    n = mesh_mod.mesh_axis_size(axis)
    spec = list(base_spec) + [None] * (len(shape) - len(base_spec))
    if n <= 1:
        return P(*spec)
    for i, (dim, s) in enumerate(zip(shape, spec)):
        if s is None and dim % n == 0 and dim >= n:
            spec[i] = axis
            break
    return P(*spec)


class LlamaSpmdTrainer:
    def __init__(self, config: LlamaConfig, lr=3e-4, weight_decay=0.1,
                 beta1=0.9, beta2=0.95, eps=1e-8, remat=True,
                 n_micro=None, seed=0, compute_dtype=jnp.bfloat16,
                 from_state_dict=None, remat_policy="full",
                 n_virtual=1, remat_stage=False,
                 moments_dtype=jnp.float32, ce_remat=True,
                 scan_unroll=1):
        self.config = config
        self.lr = lr
        self.wd = weight_decay
        self.b1, self.b2, self.eps = beta1, beta2, eps
        self.remat = remat
        # 'full': recompute everything in backward (min memory);
        # 'save_dots': keep tagged matmul outputs so backward recompute is
        # mostly elementwise — except the dense attention path (sep>1/CPU),
        # whose O(T^2) QK^T/softmax is rematerialized either way
        # (the reference's recompute granularity knob, RecomputeConfig);
        # 'save_attn': keep only q/k/v/attn_out (what the flash backward
        # reads) and recompute the MLP — the long-context point between
        # 'full' and 'save_dots' where the ffn_gate/ffn_up buffers
        # (2.7x hidden per token) dominate the saved bytes
        if remat_policy not in ("full", "save_dots", "save_attn"):
            raise ValueError(f"remat_policy must be 'full', 'save_dots' "
                             f"or 'save_attn', got {remat_policy!r}")
        self.remat_policy = remat_policy
        self.compute_dtype = compute_dtype
        # AdamW moment storage dtype. fp32 is the default (exact parity
        # with the reference's Adam); bf16 halves optimizer-state HBM
        # (the update math still runs in fp32 — only m/v storage is
        # compressed, master weights stay fp32). The memory-efficient
        # analog of the reference's multi_precision knob.
        self.moments_dtype = moments_dtype
        # ce_remat=True recomputes each CE chunk's logits in backward
        # (min memory); False saves the bf16 chunk logits instead —
        # one head-matmul less recompute when HBM allows
        self.ce_remat = ce_remat
        # unroll factor for the scan over a stage's layers: >1 removes
        # the XLA while-loop (its double-buffered carries and per-layer
        # weight dynamic-slices) at the cost of compile time — worth it
        # for shallow stages
        self.scan_unroll = int(scan_unroll)
        mesh = mesh_mod.get_mesh()
        self.pp = mesh.shape.get("pp", 1)
        self.n_micro = n_micro or max(2 * self.pp, 1)
        # interleaved virtual stages (ref PipelineParallelWithInterleave,
        # pipeline_parallel.py:551): each stage owns n_virtual
        # non-adjacent chunks
        self.n_virtual = int(n_virtual)
        self.remat_stage = remat_stage
        L = config.num_hidden_layers
        n_chunks = self.pp * self.n_virtual
        assert L % n_chunks == 0, \
            "layers must divide pp_degree * n_virtual"
        self.layers_per_stage = L // n_chunks
        # Optional single-chip pallas path: fused rmsnorm+residual and
        # fused AdamW (one HBM pass each). OPT-IN via
        # FLAGS_tpu_fused_block=pallas: measured on v5e, XLA's own fusion
        # of the jnp path is faster in the full training graph (a pallas
        # custom call is a fusion barrier), so the default stays 'xla'.
        # Multi-chip GSPMD always uses jnp — pallas_call doesn't
        # partition under GSPMD without a fully-manual shard_map region.
        from ..flags import get_flag
        self._pallas_fused = (
            on_tpu() and mesh.size == 1
            and get_flag("FLAGS_tpu_fused_block", "xla") == "pallas")
        self.head_dim = config.hidden_size // config.num_attention_heads
        self._stepno = 0
        self.params = self._init_params(seed)
        self.opt_state = self._init_opt_state()
        self._step_fn = None

    # -- parameters ---------------------------------------------------------
    def _param_specs(self):
        c = self.config
        H = c.hidden_size
        KV = c.num_key_value_heads * self.head_dim
        F = c.intermediate_size
        # block leaves all carry leading dims [pp, layers_per_stage, ...]
        blk = {
            "wq": ((H, H), (None, "mp")),
            "wk": ((H, KV), (None, "mp")),
            "wv": ((H, KV), (None, "mp")),
            "wo": ((H, H), ("mp", None)),
            "wg": ((H, F), (None, "mp")),
            "wu": ((H, F), (None, "mp")),
            "wd": ((F, H), ("mp", None)),
            "ln1": ((H,), (None,)),
            "ln2": ((H,), (None,)),
        }
        return blk

    def _init_params(self, seed):
        c = self.config
        key = jax.random.PRNGKey(seed)
        dt = self.compute_dtype
        H, V = c.hidden_size, c.vocab_size
        keys = jax.random.split(key, 4 + len(self._param_specs()))
        std = 0.02

        def init(k, shape, spec, scale=std, ones=False, rearrange=None):
            if ones:
                # add 0 to escape jnp's constant cache: donated buffers must
                # be unique
                a = jnp.ones(shape, dt) + jnp.zeros((), dt)
            else:
                a = (scale * jax.random.normal(k, shape)).astype(dt)
            if rearrange is not None:
                a = rearrange(a)
            return _place(a, *spec)

        params = {
            "embed": init(keys[0], (V, H), ("mp", None)),
            "norm": init(keys[1], (H,), (None,), ones=True),
            "head": init(keys[2], (H, V), (None, "mp")),
        }
        blocks = {}
        blk_specs = self._param_specs()
        staged = self.n_virtual > 1 and self.pp > 1
        for i, (name, (shape, spec)) in enumerate(blk_specs.items()):
            # leading dim = logical chunks (pp * n_virtual), pp-sharded;
            # with interleave the chunks are rearranged ONCE here into the
            # staged [pp, v, ...] layout (per-step rearrangement would
            # shuffle weights across pp shards every step)
            full_shape = (self.pp * self.n_virtual,
                          self.layers_per_stage) + shape
            full_spec = (("pp", None, None) if staged else
                         ("pp", None)) + spec
            ones = name.startswith("ln")
            from ..parallel.pipeline import interleave_stage_params
            blocks[name] = init(
                keys[3 + i], full_shape, full_spec, scale=std, ones=ones,
                rearrange=(functools.partial(
                    interleave_stage_params, n_stages=self.pp,
                    n_virtual=self.n_virtual) if staged else None))
        params["blocks"] = blocks
        return params

    def _init_opt_state(self):
        def init_state(a):
            shape = a.shape
            base = a.sharding.spec if isinstance(a.sharding,
                                                 NamedSharding) else ()
            spec = _zero_spec(shape, tuple(base))
            mdt = self.moments_dtype
            def zeros():
                # fresh buffer per accumulator (escape the constant cache)
                return jnp.zeros(shape, mdt) + jnp.zeros((), mdt)
            return {
                "m": mesh_mod.shard_tensor_data(zeros(), spec),
                "v": mesh_mod.shard_tensor_data(zeros(), spec),
                "master": mesh_mod.shard_tensor_data(
                    a.astype(jnp.float32) + jnp.zeros((), jnp.float32),
                    spec),
            }
        return jax.tree_util.tree_map(init_state, self.params,
                                      is_leaf=lambda x: hasattr(x, "shape"))

    # -- model math ---------------------------------------------------------
    def _rope(self, T, offset=0):
        d = self.head_dim
        inv = 1.0 / (self.config.rope_theta **
                     (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        # offset may be traced (axis_index under sequence parallelism)
        t = jnp.arange(T, dtype=jnp.float32) + offset
        freqs = jnp.outer(t, inv)
        emb = jnp.concatenate([freqs, freqs], axis=-1)
        return jnp.cos(emb), jnp.sin(emb)

    def _block(self, bp, x):
        """One decoder block. x: [B, T, H] (dp on B, sep on T).

        Runs in two sharding regimes: plain GSPMD (pp==1), where T is the
        global sequence and 'sep' sharding is a constraint; or inside the
        pipeline's shard_map where 'sep' is a MANUAL axis (jax cannot nest
        new manual axes), T is the per-shard chunk, and rope/attention use
        global positions via axis_index('sep')."""
        c = self.config
        nh = c.num_attention_heads
        nkv = c.num_key_value_heads
        hd = self.head_dim
        dt = x.dtype
        B, T, H = x.shape
        sep_manual = (mesh_mod.mesh_axis_size("sep") > 1
                      and mesh_mod.inside_spmd_region("sep"))

        # under a manual 'sep' the T dim is structurally local;
        # mesh_mod.constraint drops manual-axis entries automatically
        cstr = mesh_mod.constraint

        def rms(h, w):
            h32 = h.astype(jnp.float32)
            out = h32 * jax.lax.rsqrt(
                jnp.mean(h32 * h32, axis=-1, keepdims=True)
                + c.rms_norm_eps)
            return (out * w.astype(jnp.float32)).astype(dt)

        from jax.ad_checkpoint import checkpoint_name

        if self._pallas_fused:
            from ..ops.pallas.fused_norm import fused_rms_norm
            h = fused_rms_norm(x, bp["ln1"], c.rms_norm_eps)
        else:
            h = rms(x, bp["ln1"])
        q = checkpoint_name((h @ bp["wq"]), "q").reshape(B, T, nh, hd)
        k = checkpoint_name((h @ bp["wk"]), "k").reshape(B, T, nkv, hd)
        v = checkpoint_name((h @ bp["wv"]), "v").reshape(B, T, nkv, hd)
        offset = jax.lax.axis_index("sep") * T if sep_manual else 0
        cos, sin = self._rope(T, offset)
        cos = cos[None, :, None, :].astype(dt)
        sin = sin[None, :, None, :].astype(dt)

        def rot(u):
            u1, u2 = jnp.split(u, 2, axis=-1)
            return jnp.concatenate([-u2, u1], axis=-1)

        q = q * cos + rot(q) * sin
        k = k * cos + rot(k) * sin

        scale = 1.0 / math.sqrt(hd)
        sep_n = mesh_mod.mesh_axis_size("sep")
        from ..flags import get_flag
        use_flash = (on_tpu() and hd % 64 == 0 and T % 128 == 0
                     and sep_n == 1
                     and bool(get_flag("FLAGS_tpu_flash_attention", True)))
        if sep_n > 1:
            # sequence parallel: q/k/v all stay sep-sharded on T; ring
            # attention circulates K/V blocks over the sep axis — per-step
            # score memory O((T/sep)^2), never a full K/V gather
            from ..parallel.ring_attention import ring_attention
            q = cstr(q, "dp", "sep", "mp", None)
            k = cstr(k, "dp", "sep", "mp", None)
            v = cstr(v, "dp", "sep", "mp", None)
            attn = ring_attention(q, k, v, causal=True, sm_scale=scale)
        elif use_flash:
            if nkv != nh:
                # the tuned kernel wants equal head counts
                rep = nh // nkv
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            attn = _flash_per_shard(q, k, v, scale)
        elif nkv != nh:
            # grouped-query attention without materializing repeated K/V:
            # fold the group dim into the score einsum (g = nh // nkv)
            g = nh // nkv
            qg = q.reshape(B, T, nkv, g, hd)
            scores = jnp.einsum("bqngd,bknd->bngqk", qg, k,
                                preferred_element_type=jnp.float32) * scale
            mask = jnp.tril(jnp.ones((T, T), bool))
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            attn = jnp.einsum("bngqk,bknd->bqngd", probs, v)
            attn = attn.reshape(B, T, nh, hd)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32) * scale
            mask = jnp.tril(jnp.ones((T, T), bool))
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        attn = checkpoint_name(attn.reshape(B, T, nh * hd), "attn_out")
        if self._pallas_fused:
            # fused residual-add + rmsnorm: one HBM pass (the reference's
            # fused_layernorm_residual_dropout_bias pattern)
            from ..ops.pallas.fused_norm import fused_rms_norm_residual
            h, x = fused_rms_norm_residual(attn @ bp["wo"], x, bp["ln2"],
                                           c.rms_norm_eps)
        else:
            x = x + attn @ bp["wo"]
            h = rms(x, bp["ln2"])
        gate = jax.nn.silu(checkpoint_name(h @ bp["wg"], "ffn_gate"))
        up = checkpoint_name(h @ bp["wu"], "ffn_up")
        x = x + (gate * up) @ bp["wd"]
        return cstr(x, "dp", "sep", None)

    def _stage_fn(self, stage_params, x):
        """Run this stage's layers_per_stage blocks (scan + remat)."""
        block = self._block
        # remat_stage checkpoints the whole stage in the pipeline; nesting
        # per-block checkpoints under it would recompute blocks twice in
        # backward for no extra memory win. With pp==1 no pipeline (and no
        # stage-level checkpoint) runs, so block remat must stay on.
        stage_remat_active = self.remat_stage and self.pp > 1
        if self.remat and not stage_remat_active:
            if self.remat_policy == "save_dots":
                pol = jax.checkpoint_policies.save_only_these_names(
                    "q", "k", "v", "attn_out", "ffn_gate", "ffn_up")
                block = jax.checkpoint(block, policy=pol)
            elif self.remat_policy == "save_attn":
                pol = jax.checkpoint_policies.save_only_these_names(
                    "q", "k", "v", "attn_out")
                block = jax.checkpoint(block, policy=pol)
            else:
                block = jax.checkpoint(block)

        def body(carry, bp):
            return block(bp, carry), None

        out, _ = jax.lax.scan(body, x, stage_params,
                              unroll=max(1, self.scan_unroll))
        return out

    def forward(self, params, ids):
        """ids: [B, T] -> logits [B, T, V]."""
        x = self.forward_hidden(params, ids)
        logits = x @ params["head"]
        return mesh_mod.constraint(logits, "dp", "sep", "mp")

    def forward_hidden(self, params, ids):
        """ids: [B, T] -> final-norm hidden states [B, T, H] (pre-head)."""
        x = jnp.take(params["embed"], ids, axis=0).astype(self.compute_dtype)
        x = mesh_mod.constraint(x, "dp", "sep", None)
        if self.pp > 1:
            B = x.shape[0]
            assert B % self.n_micro == 0, "batch must divide n_micro"
            mb = B // self.n_micro
            x_micro = x.reshape((self.n_micro, mb) + x.shape[1:])
            sep_n = mesh_mod.mesh_axis_size("sep")
            kw = dict(n_virtual=self.n_virtual,
                      remat_stage=self.remat_stage)
            if sep_n > 1:
                kw.update(manual_axes={"sep"},
                          x_spec=P(None, None, "sep"))
            out = spmd_pipeline(self._stage_fn, params["blocks"], x_micro,
                                params_layout="staged" if
                                self.n_virtual > 1 else "logical", **kw)
            x = out.reshape((B,) + out.shape[2:])
        else:
            stage = jax.tree_util.tree_map(
                lambda a: a.reshape((-1,) + a.shape[2:]), params["blocks"])
            x = self._stage_fn(stage, x)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, -1, keepdims=True) + self.config.rms_norm_eps)
        return (x32 * params["norm"].astype(jnp.float32)).astype(
            self.compute_dtype)

    def loss_fn(self, params, ids, labels):
        """Next-token cross entropy, computed CHUNKED over the sequence:
        each lax.scan step projects one T-chunk through the vocab head
        and reduces it to per-token CE (logsumexp - target logit) in
        fp32, under jax.checkpoint so backward recomputes the chunk
        logits instead of saving them. Peak loss memory drops from
        2 full fp32 [B, T, V] buffers (logits + log_softmax) to one
        [B, C, V] chunk — the difference between OOM and fitting a
        bigger batch at vocab 32000 on one chip. Numerics are identical
        to log_softmax + gather (same fp32 logsumexp)."""
        if mesh_mod.mesh_axis_size("sep") > 1:
            # sequence parallel: T is sep-sharded (chunking would fight
            # GSPMD over the reshape) and the per-device logit slab is
            # already T/sep small — use the plain log_softmax path
            logits = self.forward(params, ids).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            tgt = labels[:, 1:]
            picked = jnp.take_along_axis(logp, tgt[..., None], axis=-1)
            return -picked.mean()
        x = self.forward_hidden(params, ids)          # [B, T, H]
        B, T, H = x.shape
        head = params["head"]
        # position t predicts labels[t+1]; the final position has no
        # target — give it a dummy and mask it out of the mean
        tgt = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1)
        C = min(256, T)
        while T % C:
            C //= 2
        nC = T // C
        xs = jnp.moveaxis(x.reshape(B, nC, C, H), 1, 0)       # [nC,B,C,H]
        ts = jnp.moveaxis(tgt.reshape(B, nC, C), 1, 0)        # [nC,B,C]

        def chunk_ce(xc, tc):
            logits = (xc @ head).astype(jnp.float32)          # [B, C, V]
            logits = mesh_mod.constraint(logits, "dp", None, "mp")
            lse = jax.nn.logsumexp(logits, axis=-1)           # [B, C]
            picked = jnp.take_along_axis(
                logits, tc[..., None], axis=-1)[..., 0]
            return lse - picked                               # [B, C]

        def body(total, xc_tc):
            return total + chunk_ce(*xc_tc).sum(axis=-1), None

        if nC > 1:
            b = jax.checkpoint(body) if self.ce_remat else body
            ce_rows, _ = jax.lax.scan(b, jnp.zeros((B,), jnp.float32),
                                      (xs, ts))
            # subtract the masked final position's dummy CE
            ce_rows = ce_rows - chunk_ce(x[:, -1:], tgt[:, -1:])[:, 0]
        else:
            ce = chunk_ce(x, tgt)                             # [B, T]
            ce_rows = ce[:, :-1].sum(axis=-1)
        return ce_rows.sum() / (B * (T - 1))

    # -- optimizer ----------------------------------------------------------
    def _adamw(self, p, g, st, lr, step):
        if self._pallas_fused and self.moments_dtype == jnp.float32:
            # one fused pallas pass over p/g/m/v/master (the reference's
            # fused_adam multi-tensor kernel, fused_adam_kernel.cu)
            from ..ops.pallas.fused_adamw import fused_adamw_update
            new_p, m, v, master = fused_adamw_update(
                p, g, st["m"], st["v"], st["master"], lr, self.b1,
                self.b2, self.eps, self.wd, step)
            return new_p, {"m": m, "v": v, "master": master}
        g32 = g.astype(jnp.float32)
        m = self.b1 * st["m"].astype(jnp.float32) + (1 - self.b1) * g32
        v = (self.b2 * st["v"].astype(jnp.float32)
             + (1 - self.b2) * g32 * g32)
        mh = m / (1 - self.b1 ** step)
        vh = v / (1 - self.b2 ** step)
        upd = mh / (jnp.sqrt(vh) + self.eps) + self.wd * st["master"]
        master = st["master"] - lr * upd
        mdt = self.moments_dtype
        return master.astype(p.dtype), {"m": m.astype(mdt),
                                        "v": v.astype(mdt),
                                        "master": master}

    def _make_step(self):
        def step(params, opt_state, ids, labels, lr, stepno):
            loss, grads = jax.value_and_grad(self.loss_fn)(params, ids,
                                                           labels)
            leaves_p, tree = jax.tree_util.tree_flatten(params)
            leaves_g = jax.tree_util.tree_leaves(grads)
            leaves_s = tree.flatten_up_to(opt_state)
            new_p, new_s = [], []
            for p, g, st in zip(leaves_p, leaves_g, leaves_s):
                np_, ns = self._adamw(p, g, st, lr, stepno)
                new_p.append(np_)
                new_s.append(ns)
            return (loss, jax.tree_util.tree_unflatten(tree, new_p),
                    jax.tree_util.tree_unflatten(tree, new_s))
        return jax.jit(step, donate_argnums=(0, 1))

    def train_step(self, ids, labels=None):
        if labels is None:
            labels = ids
        if self._step_fn is None:
            self._step_fn = self._make_step()
        self._stepno += 1
        ids = _place(jnp.asarray(ids), "dp", None)
        labels = _place(jnp.asarray(labels), "dp", None)
        loss, self.params, self.opt_state = self._step_fn(
            self.params, self.opt_state, ids, labels,
            jnp.asarray(self.lr, jnp.float32),
            jnp.asarray(self._stepno, jnp.float32))
        return loss

    # -- analytics ----------------------------------------------------------
    def flops_per_token(self, seq_len=None):
        """Training FLOPs/token, strict Megatron/PaLM convention:

        - 6 * params-in-matmuls, where the vocab projection is counted
          ONCE (the logit head V*H). The input-embedding forward is a
          gather and its backward a scatter-add — no matmul FLOPs, so the
          untied embedding table contributes nothing here even though the
          hardware does real (uncounted) work for it.
        - causal attention quadratic term: QK^T and PV are 2*H*T_eff fwd
          flops each per token with T_eff = T/2 under causal masking;
          backward doubles the forward, so train = 3x fwd = 6*H*T per
          layer per token.
        - Remat recompute is NOT counted (MFU convention: model FLOPs
          only).
        """
        c = self.config
        H, F, V = c.hidden_size, c.intermediate_size, c.vocab_size
        T = seq_len or c.max_position_embeddings
        KV = c.num_key_value_heads * self.head_dim
        per_layer = 2 * H * H + 2 * H * KV + 3 * H * F
        matmul_params = c.num_hidden_layers * per_layer + V * H
        attn = 6 * c.num_hidden_layers * H * T
        return 6 * matmul_params + attn

    def param_count(self):
        return sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(self.params))
