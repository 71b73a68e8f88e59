"""ONE jitted shard_map program per sharded serving step.

PR 15's ShardedServingCore is host-staged: per layer, per shard,
Python issues the qkv GEMM, the paged-attention launch and a
device_put-hopping all-reduce — O(shards x layers) dispatches per
model call, and the reduced tensor round-trips host numpy. This
module lowers the SAME schedule (the one PR 15 proved bit-exact:
disjoint zero-padded head sums closed by exactly one collective per
layer) into a single ``jax.jit(shard_map(body))`` program over a
``Mesh(("mp",))`` — the GSPMD programming model (PAPERS.md, arxiv
2105.04663) applied to the serving stack:

  * the per-shard KV pools ride as DONATED, head-sharded arguments
    (``NamedSharding(P(None, None, "mp", None, None))`` on the
    ``[num_blocks, 2, H/mp, bs, D]`` pools; int8 scale pages
    alongside) — append-scatter and attention read/write
    device-resident state, zero host round-trips. Assembly of the
    global array from the cache's per-shard entries and the rebind
    from the donated outputs are both zero-copy metadata ops
    (``jax.make_array_from_single_device_arrays`` /
    ``addressable_shards``), so ``PagedKVCache`` keeps its flat
    per-shard list — COW splits, prefill scatters, snapshots and
    slice export between compiled calls see ordinary committed
    per-device arrays and need no changes.
  * inside the mapped body each layer runs per-shard qkv + the
    per-segment attention decomposition and closes with EXACTLY ONE
    ``jax.lax.psum``. Two closure modes (``out_shard``):
    ``"replicated"`` psums the zero-padded disjoint head sums and
    runs the out-projection replicated — IEEE-exact (x + 0 == x;
    each element has one nonzero contributor), the CPU-proof twin of
    the legacy ``_allreduce``; ``"rows"`` is the true Megatron
    second GEMM — each shard multiplies its head slice against its
    ROW slice of ``out_proj.weight`` and psums the partial sums.
    Rows mode belongs on the compiled path (TPU default): a K-split
    GEMM is not column-stable on CPU at serving widths, the same
    trap class as ``qkv_shard="activations"``.
  * the CPU attention body is the EXACT per-segment decomposition
    the eager views run (one multi-row masked sdpa per prefill
    chunk, batch-of-1-row sdpa for decode, the L-fold for verify
    rows — ``_sdpa_jnp`` itself), so compiled mp=N streams stay
    bit-identical to the mp=1 eager engine. On TPU the ragged pallas
    kernel slots into the same body (ROADMAP hardware leg);
    ``paged_attention_ragged`` is already callable under shard_map.

Compile-cache discipline: programs are cached per STATIC BUCKET key.
Prefill chunk lengths bucket to the next power of two (minimum 2;
length-1 chunks stay singleton — padding a 1-row chunk to 2 rows
would swap the GEMV-class sdpa for the multi-row one, the
MIN_PREFILL_SUFFIX_ROWS trap in reverse), with pad rows routed to
the trash block on write and dropped on unpack; decode/verify
segments are naturally static ``(B, L)``. Retrace count ==
``len(self._fns)`` is exported through ``sharded.retraces`` and
bounded in tests. Pad/unpack row gathers run EAGERLY outside the
program (tiny ops, cached per shape by jax itself) so real row
counts never leak into the program key.

HOT-PATH PURITY (tools/check_static.py ``compiled-step-purity``):
nothing on the per-step call path — ``forward`` / ``_run_*`` /
``_dispatch`` / the traced bodies — may pull device data to host
(``np.asarray``/``device_get``/``.item``/...) or hop devices
(``device_put``). Host metadata (numpy routing built from the
layout's np fields) flows IN via ``jnp.asarray`` as operands; that
direction is the normal feed and is allowed. Setup (``__init__``,
``_setup_weights``) is the allowlisted boundary where weights are
placed once.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework.tensor import Tensor
from ..nn.functional.attention import _sdpa_jnp
from ..ops.pallas.paged_attention import gather_pages

_POOL_SPEC = P(None, None, "mp", None, None)
_SCALE_SPEC = P(None, None, "mp", None)


def _bucket(n: int) -> int:
    """Prefill-chunk length bucket: next power of two, minimum 2 —
    EXCEPT length 1, which stays 1 (a 1-row sdpa is the GEMV-class
    executable; padding it to 2 rows would change its bits vs the
    eager step, the same accumulation trap MIN_PREFILL_SUFFIX_ROWS
    exists for)."""
    n = int(n)
    if n <= 1:
        return n
    return max(2, 1 << (n - 1).bit_length())


def _act_fn(name: str):
    if name == "gelu":
        # F.gelu's default: exact (erf) gelu, not the tanh approximation
        return lambda a: jax.nn.gelu(a, approximate=False)
    f = getattr(jax.nn, name, None)
    if f is None:
        raise ValueError(f"activation {name!r} has no jax.nn twin")
    return f


def _ln(x, w, b, eps):
    # mirror of nn/functional/norm.py layer_norm at normalized_shape
    # == [E]: float32 mean/var, rsqrt, affine, cast back
    mean = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
    var = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True)
    out = (x.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + eps)
    if w is not None:
        out = out * w.astype(jnp.float32).reshape([x.shape[-1]])
    if b is not None:
        out = out + b.astype(jnp.float32).reshape([x.shape[-1]])
    return out.astype(x.dtype)


def _linear(a, w, b):
    # mirror of nn/functional/common.py linear
    if b is None:
        return a @ w
    return a @ w + b


def _count_psums(fn, args) -> int:
    """Trace ``fn`` and count psum primitives in the jaxpr (recursing
    into sub-jaxprs) — the traced-lowering collective count the
    dispatch instrumentation exports as ``sharded.psums_per_call``."""
    jaxpr = jax.make_jaxpr(fn)(*args)

    def walk(jx) -> int:
        n = 0
        for eqn in jx.eqns:
            if "psum" in eqn.primitive.name:
                n += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        n += walk(inner)
                    elif hasattr(sub, "eqns"):
                        n += walk(sub)
        return n
    return walk(jaxpr.jaxpr)


class CompiledStepRunner:
    """Per-core compiler + program cache + dispatch counters for the
    compiled sharded serving step. Owns the serving Mesh(("mp",)),
    the pre-placed weight pytree, and one jitted program per static
    bucket key. ``ShardedServingCore.forward`` hands it every paged
    call when ``compiled_step`` engages; it returns the hidden
    states + (unchanged) views, with the cache's per-shard pool
    entries rebound to the donated outputs' shards."""

    def __init__(self, core):
        from ..parallel.mesh import serving_mesh
        mesh = serving_mesh(core.mp, core.shard_devices)
        if mesh is None:
            raise ValueError(
                "compiled_step needs mp distinct shard devices (a "
                "real mesh); logical shards on one device stay on "
                "the legacy host-staged path")
        self.core = core
        self.mesh = mesh
        self._fns: Dict[tuple, tuple] = {}      # key -> (fn, psums)
        self.jit_calls = 0
        self.last_dispatches = 0
        self._last_psums = 0
        self._weights: Optional[list] = None
        self._wspecs: Optional[list] = None
        self._ln_eps: List[float] = []
        self._ffn_ln_eps: List[float] = []
        self._pool_sh = NamedSharding(self.mesh, _POOL_SPEC)
        self._scale_sh = NamedSharding(self.mesh, _SCALE_SPEC)

    # -- counters (MetricsRegistry surface) ---------------------------
    @property
    def retraces(self) -> int:
        return len(self._fns)

    def metrics(self) -> dict:
        return {"jit_calls": self.jit_calls,
                "retraces": self.retraces,
                "dispatches_per_step": self.last_dispatches,
                "psums_per_call": self._last_psums}

    def reset_counters(self) -> None:
        self.jit_calls = 0
        self.last_dispatches = 0

    # -- weight placement (setup boundary: runs once) -----------------
    def _setup_weights(self) -> None:
        core = self.core
        mesh = self.mesh
        repl = NamedSharding(mesh, P())

        def put(t):
            return None if t is None else jax.device_put(t.data, repl)

        W, S = [], []
        for i, blk in enumerate(core.base.layers):
            w, s = {}, {}

            def keep(name, arr, spec=P()):
                if arr is not None:
                    w[name] = arr
                    s[name] = spec
            keep("ln_w", put(blk.ln.weight))
            keep("ln_b", put(blk.ln.bias))
            keep("ffn_ln_w", put(blk.ffn_ln.weight))
            keep("ffn_ln_b", put(blk.ffn_ln.bias))
            self._ln_eps.append(float(blk.ln._epsilon))
            self._ffn_ln_eps.append(float(blk.ffn_ln._epsilon))
            if core.qkv_shard == "weights":
                # reuse the core's per-shard column slices, already
                # committed one per device — assembly is zero-copy.
                # The global column order interleaves shards' q/k/v
                # blocks, which is irrelevant: the body only ever
                # sees its LOCAL [E, 3*Hs*hd] slice.
                parts = [core._qkv_w[i][s_].data
                         for s_ in range(core.mp)]
                E = parts[0].shape[0]
                width = sum(p.shape[1] for p in parts)
                keep("qkv_w", jax.make_array_from_single_device_arrays(
                    (E, width), NamedSharding(mesh, P(None, "mp")),
                    parts), P(None, "mp"))
                if core._qkv_b[i][0] is not None:
                    bparts = [core._qkv_b[i][s_].data
                              for s_ in range(core.mp)]
                    keep("qkv_b", jax.make_array_from_single_device_arrays(
                        (width,), NamedSharding(mesh, P("mp")),
                        bparts), P("mp"))
            else:
                keep("qkv_w", put(blk.qkv.weight))
                keep("qkv_b", put(blk.qkv.bias))
            if core.out_shard == "rows":
                # true Megatron second GEMM: shard s owns the row
                # block [s*Hs*hd, (s+1)*Hs*hd) — contiguous because
                # att.reshape(..., H*hd) orders (head, dim) and
                # shards hold contiguous head ranges
                keep("out_w", jax.device_put(
                    blk.out_proj.weight.data,
                    NamedSharding(mesh, P("mp", None))), P("mp", None))
            else:
                keep("out_w", put(blk.out_proj.weight))
            keep("out_b", put(blk.out_proj.bias))
            keep("ffn1_w", put(blk.ffn1.weight))
            keep("ffn1_b", put(blk.ffn1.bias))
            keep("ffn2_w", put(blk.ffn2.weight))
            keep("ffn2_b", put(blk.ffn2.bias))
            W.append(w)
            S.append(s)
        self._weights = W
        self._wspecs = S

    # -- pool assembly / rebind (zero-copy both ways) -----------------
    def _assemble(self, cache) -> Tuple[list, list]:
        L, mp = cache.num_layers, cache.mp
        Hs = cache.heads_per_shard
        pshape = (cache.num_blocks, 2, Hs * mp, cache.block_size,
                  cache.head_dim)
        pools = [jax.make_array_from_single_device_arrays(
            pshape, self._pool_sh,
            [cache.pools[cache.pool_index(li, s)].data
             for s in range(mp)]) for li in range(L)]
        if not cache.quantized:
            return pools, []
        sshape = pshape[:3] + (cache.block_size,)
        scales = [jax.make_array_from_single_device_arrays(
            sshape, self._scale_sh,
            [cache.scales[cache.pool_index(li, s)].data
             for s in range(mp)]) for li in range(L)]
        return pools, scales

    # -- program build ------------------------------------------------
    def _get_fn(self, key, meta, pools_g, scales_g, ops):
        hit = self._fns.get(key)
        if hit is not None:
            return hit
        if self._weights is None:
            self._setup_weights()
        body = self._make_body(meta)
        nl = self.core.num_layers
        pool_specs = [_POOL_SPEC] * nl
        scale_specs = [_SCALE_SPEC] * nl if meta["quantized"] else []
        ops_spec = jax.tree_util.tree_map(lambda _: P(), ops)
        smap = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(pool_specs, scale_specs, self._wspecs, ops_spec),
            out_specs=(P(), pool_specs, scale_specs),
            check_vma=False)
        psums = _count_psums(smap, (pools_g, scales_g, self._weights,
                                    ops))
        fn = jax.jit(smap, donate_argnums=(0, 1))
        self._fns[key] = (fn, psums)
        return fn, psums

    def _dispatch(self, key, meta, cache, ops):
        """Assemble pools -> run the (cached) program -> rebind the
        cache's per-shard entries from the donated outputs. Returns
        the hidden states (global, replicated)."""
        pools_g, scales_g = self._assemble(cache)
        fn, psums = self._get_fn(key, meta, pools_g, scales_g, ops)
        hidden, new_pools, new_scales = fn(pools_g, scales_g,
                                           self._weights, ops)
        # donation invalidated the input buffers: rebind IMMEDIATELY
        # so no eager path can touch a dead pool entry
        for li in range(cache.num_layers):
            cache.rebind_shard_pools(
                li, new_pools[li],
                new_scales[li] if new_scales else None)
        self.jit_calls += 1
        self.last_dispatches = 1
        self._last_psums = psums
        return hidden

    # -- entry: view-type dispatch ------------------------------------
    def forward(self, src, caches, time_step):
        """Serve one model call through the compiled program. Returns
        (hidden Tensor, caches) or None when the view type is not
        one the compiled step serves (the caller falls back to the
        legacy host-staged loop)."""
        from .paged_cache import (PagedLayerCache, PagedPrefillView,
                                  PagedRaggedView)
        v0 = caches[0]
        if isinstance(v0, PagedRaggedView):
            return self._run_ragged(src, caches)
        if isinstance(v0, PagedPrefillView):
            return self._run_chunk(src, caches, time_step)
        if isinstance(v0, PagedLayerCache):
            return self._run_decode(src, caches, time_step)
        return None

    def _norm_t(self, time_step, b):
        t = time_step.data if isinstance(time_step, Tensor) \
            else jnp.asarray(time_step, jnp.int32)
        return jnp.broadcast_to(t.reshape(-1).astype(jnp.int32), (b,))

    def _geom(self, cache) -> dict:
        core = self.core
        return {"quantized": bool(cache.quantized),
                "bs": cache.block_size,
                "MB": cache.max_blocks_per_seq,
                "E": core.embed_dim, "H": core.num_heads,
                "Hs": core.heads_per_shard, "hd": core.head_dim,
                "nlayers": core.num_layers,
                "qkv_mode": core.qkv_shard,
                "out_mode": core.out_shard,
                "act": core._act_name,
                "normalize_before": bool(core.normalize_before)}

    # -- ragged (packed mixed step) -----------------------------------
    def _run_ragged(self, src, caches):
        lay = caches[0]._layout
        cache = caches[0]._cache
        R_real = lay.total_rows
        segs_static: List[tuple] = []
        pad_idx: List[int] = []     # padded row -> [0, R_real] (R_real = zero row)
        real_idx: List[int] = []    # packed row -> its padded position
        blk_pad: List[np.ndarray] = []
        off_pad: List[np.ndarray] = []
        starts: List[int] = []
        lens_np = None
        lo_pad = 0
        for seg in lay.segs:
            kind, lo, hi = seg[0], seg[1], seg[2]
            n = hi - lo
            if kind == "prefill":
                cpad = _bucket(n)
                segs_static.append(("p", cpad))
                starts.append(int(seg[4]))
                pad_idx.extend(range(lo, hi))
                pad_idx.extend([R_real] * (cpad - n))
                real_idx.extend(range(lo_pad, lo_pad + n))
                blk_pad.append(lay.blk_np[lo:hi])
                off_pad.append(lay.off_np[lo:hi])
                if cpad > n:
                    # pad rows write the trash block at offset 0 —
                    # duplicate indices there are fine, nothing reads
                    # it unmasked (same rule as adopted-prefix rows)
                    blk_pad.append(np.zeros(cpad - n, np.int32))
                    off_pad.append(np.zeros(cpad - n, np.int32))
                lo_pad += cpad
            else:
                lens_np, L = seg[3], seg[4]
                B = n // L
                segs_static.append(("d", B, L))
                pad_idx.extend(range(lo, hi))
                real_idx.extend(range(lo_pad, lo_pad + n))
                blk_pad.append(lay.blk_np[lo:hi])
                off_pad.append(lay.off_np[lo:hi])
                lo_pad += n
        R_pad = lo_pad
        meta = self._geom(cache)
        meta.update(kind="ragged", segs=tuple(segs_static))
        key = ("ragged", meta["segs"], meta["quantized"])

        x0 = src.data[0]
        if R_pad != R_real:
            xz = jnp.concatenate(
                [x0, jnp.zeros((1, x0.shape[-1]), x0.dtype)], axis=0)
            xp = jnp.take(xz, jnp.asarray(pad_idx, np.int32),
                          axis=0)[None]
        else:
            xp = src.data
        ops = {"x": xp,
               "blk": jnp.asarray(np.concatenate(blk_pad)
                                  .astype(np.int32)),
               "off": jnp.asarray(np.concatenate(off_pad)
                                  .astype(np.int32)),
               "bt": lay.bt_all.data}
        if starts:
            ops["starts"] = jnp.asarray(starts, jnp.int32)
        if lens_np is not None:
            ops["lens"] = jnp.asarray(lens_np, jnp.int32)
        hidden = self._dispatch(key, meta, cache, ops)
        if R_pad != R_real:
            hidden = jnp.take(hidden[0],
                              jnp.asarray(real_idx, np.int32),
                              axis=0)[None]
        return Tensor(hidden), list(caches)

    # -- chunked prefill (one slot, batch-1) --------------------------
    def _run_chunk(self, src, caches, time_step):
        view = caches[0]
        cache = view._cache
        C = int(src.shape[1])
        cpad = _bucket(C)
        meta = self._geom(cache)
        meta.update(kind="chunk", C=cpad)
        key = ("chunk", cpad, meta["quantized"])
        xp = src.data
        if cpad > C:
            xp = jnp.concatenate(
                [xp, jnp.zeros((1, cpad - C, xp.shape[-1]),
                               xp.dtype)], axis=1)
        ops = {"x": xp,
               "t": self._norm_t(time_step, 1),
               "ws": jnp.asarray([view._write_start], jnp.int32),
               "nreal": jnp.asarray([C], jnp.int32),
               "bt": cache.bt_row_tensor(view._slot).data}
        hidden = self._dispatch(key, meta, cache, ops)
        if cpad > C:
            hidden = jax.lax.slice_in_dim(hidden, 0, C, axis=1)
        return Tensor(hidden), list(caches)

    # -- fused decode / multi-token verify ----------------------------
    def _run_decode(self, src, caches, time_step):
        cache = caches[0]._cache
        B, L = int(src.shape[0]), int(src.shape[1])
        meta = self._geom(cache)
        meta.update(kind="decode", B=B, L=L)
        key = ("decode", B, L, meta["quantized"])
        ops = {"x": src.data,
               "t": self._norm_t(time_step, B),
               "bt": cache.bt_tensor().data}
        hidden = self._dispatch(key, meta, cache, ops)
        return Tensor(hidden), list(caches)

    # -- the mapped body ----------------------------------------------
    def _make_body(self, meta):
        """Build the shard_map body for one static bucket. The body
        mirrors the eager sharded step FORMULA FOR FORMULA — the
        layer_norm/linear/sdpa impls, the append-scatter routing of
        paged_cache's factories, the per-segment decomposition of
        the paged views — so the compiled program's streams are
        bit-identical to the host-staged ones on CPU. Collectives:
        exactly one psum per layer (replicated mode pads disjoint
        head sums; rows mode psums the out-GEMM partials)."""
        from .paged_cache import _quant_rows
        kind = meta["kind"]
        nl, quantized = meta["nlayers"], meta["quantized"]
        E, H, Hs, hd = meta["E"], meta["H"], meta["Hs"], meta["hd"]
        bs = meta["bs"]
        qkv_mode, out_mode = meta["qkv_mode"], meta["out_mode"]
        normalize_before = meta["normalize_before"]
        act = _act_fn(meta["act"])
        ln_eps, ffn_eps = list(self._ln_eps), list(self._ffn_ln_eps)

        def qkv(h, w, s):
            y = _linear(h, w["qkv_w"], w.get("qkv_b"))
            b_, l_ = y.shape[0], y.shape[1]
            width = y.shape[-1] // 3
            parts = [jax.lax.slice_in_dim(y, j * width,
                                          (j + 1) * width, axis=-1)
                     for j in range(3)]
            if qkv_mode == "weights":
                return [p.reshape(b_, l_, Hs, hd) for p in parts]
            full = [p.reshape(b_, l_, H, hd) for p in parts]
            return [jax.lax.dynamic_slice_in_dim(p, s * Hs, Hs,
                                                 axis=2)
                    for p in full]

        def gather(pool, bt_rows, sc):
            if sc is None:
                return gather_pages(pool, bt_rows)
            return gather_pages(pool, bt_rows, sc)

        def close_layer(li, resid, att, w):
            # att: [b, l, Hs, hd] local head slice -> one psum
            s = jax.lax.axis_index("mp")
            b_, l_ = att.shape[0], att.shape[1]
            if out_mode == "replicated":
                pad = jnp.zeros((b_, l_, H, hd), att.dtype)
                pad = jax.lax.dynamic_update_slice(
                    pad, att, (0, 0, s * Hs, 0))
                full = jax.lax.psum(pad, "mp")
                attn = _linear(full.reshape(b_, l_, E), w["out_w"],
                               w.get("out_b"))
            else:
                part = att.reshape(b_, l_, Hs * hd) @ w["out_w"]
                attn = jax.lax.psum(part, "mp")
                if w.get("out_b") is not None:
                    attn = attn + w["out_b"]
            x = resid + attn
            if not normalize_before:
                x = _ln(x, w.get("ln_w"), w.get("ln_b"), ln_eps[li])
            resid = x
            hh = _ln(x, w.get("ffn_ln_w"), w.get("ffn_ln_b"),
                     ffn_eps[li]) if normalize_before else x
            hh = _linear(act(_linear(hh, w["ffn1_w"],
                                     w.get("ffn1_b"))),
                         w["ffn2_w"], w.get("ffn2_b"))
            x = resid + hh
            if not normalize_before:
                x = _ln(x, w.get("ffn_ln_w"), w.get("ffn_ln_b"),
                        ffn_eps[li])
            return x

        def append_rows(pool, sc, k, v, blk, off):
            # a ROW scatter, where the eager views write whole pages
            # (paged_cache._write_rows): on the TPU it costs two
            # whole-pool layout copies a layer, donated or not
            # (ROADMAP S6). k/v [1, R, Hs, hd]
            if quantized:
                kq, ks = _quant_rows(k[0])
                vq, vs = _quant_rows(v[0])
                pool = pool.at[blk, 0, :, off, :].set(kq)
                pool = pool.at[blk, 1, :, off, :].set(vq)
                sc = sc.at[blk, 0, :, off].set(ks)
                sc = sc.at[blk, 1, :, off].set(vs)
                return pool, sc
            pool = pool.at[blk, 0, :, off, :].set(
                k[0].astype(pool.dtype))
            pool = pool.at[blk, 1, :, off, :].set(
                v[0].astype(pool.dtype))
            return pool, sc

        if kind == "ragged":
            segs = meta["segs"]

            def attn_ragged(pool, sc, q, ops):
                bt = ops["bt"]
                outs = []
                row = btr = p_i = 0
                for seg in segs:
                    if seg[0] == "p":
                        C = seg[1]
                        qs = q[:, row:row + C]
                        kf, vf = gather(pool, bt[btr:btr + 1], sc)
                        S = kf.shape[1]
                        qpos = (ops["starts"][p_i]
                                + jnp.arange(C)[:, None])
                        kpos = jnp.arange(S)[None, :]
                        mask = jnp.where(kpos <= qpos, 0.0,
                                         -1e30).astype(jnp.float32)
                        o = _sdpa_jnp(qs, kf, vf, mask, 0.0, False,
                                      None)
                        outs.append(o[0])
                        row += C
                        btr += 1
                        p_i += 1
                    else:
                        B, L = seg[1], seg[2]
                        lens = ops["lens"]
                        kf, vf = gather(pool, bt[btr:btr + B], sc)
                        S = kf.shape[1]
                        kpos = jnp.arange(S)[None, None, None, :]
                        if L == 1:
                            qd = q[0, row:row + B][:, None]
                            qpos = (lens[:, None, None, None]
                                    + jnp.arange(1)[None, None, :,
                                                    None])
                            mask = jnp.where(kpos <= qpos, 0.0,
                                             -1e30).astype(jnp.float32)
                            o = _sdpa_jnp(qd, kf, vf, mask, 0.0,
                                          False, None)
                        else:
                            qd = q[0, row:row + B * L][:, None]
                            kff = jnp.repeat(kf, L, axis=0)
                            vff = jnp.repeat(vf, L, axis=0)
                            tf = (jnp.repeat(lens, L)
                                  + jnp.tile(jnp.arange(
                                      L, dtype=jnp.int32), B))
                            qpos = tf[:, None, None, None]
                            mask = jnp.where(kpos <= qpos, 0.0,
                                             -1e30).astype(jnp.float32)
                            o = _sdpa_jnp(qd, kff, vff, mask, 0.0,
                                          False, None)
                        outs.append(o[:, 0])
                        row += B * L
                        btr += B
                return jnp.concatenate(outs, axis=0)[None]

            def body(pools, scales, W, ops):
                x = ops["x"]
                s = jax.lax.axis_index("mp")
                new_pools, new_scales = [], []
                for li in range(nl):
                    pool = pools[li]
                    sc = scales[li] if quantized else None
                    w = W[li]
                    resid = x
                    h = _ln(x, w.get("ln_w"), w.get("ln_b"),
                            ln_eps[li]) if normalize_before else x
                    q, k, v = qkv(h, w, s)
                    pool, sc = append_rows(pool, sc, k, v,
                                           ops["blk"], ops["off"])
                    att = attn_ragged(pool, sc, q, ops)
                    x = close_layer(li, resid, att, w)
                    new_pools.append(pool)
                    if quantized:
                        new_scales.append(sc)
                return x, new_pools, new_scales
            return body

        if kind == "chunk":
            C = meta["C"]

            def body(pools, scales, W, ops):
                x = ops["x"]
                t, ws, nreal = ops["t"], ops["ws"], ops["nreal"]
                bt = ops["bt"]
                s = jax.lax.axis_index("mp")
                # a chunk's row routing (positions below ws to trash),
                # with pad rows
                # (>= nreal) ALSO routed to the trash block
                pos = t[:, None] + jnp.arange(C, dtype=t.dtype)[None, :]
                blk = jnp.take_along_axis(bt, pos // bs, axis=1)
                rows = jnp.arange(C)[None, :]
                blk = jnp.where((pos >= ws) & (rows < nreal[0]),
                                blk, 0)
                off = pos % bs
                new_pools, new_scales = [], []
                for li in range(nl):
                    pool = pools[li]
                    sc = scales[li] if quantized else None
                    w = W[li]
                    resid = x
                    h = _ln(x, w.get("ln_w"), w.get("ln_b"),
                            ln_eps[li]) if normalize_before else x
                    q, k, v = qkv(h, w, s)
                    if quantized:
                        kq, ks = _quant_rows(k)
                        vq, vs = _quant_rows(v)
                        pool = pool.at[blk, 0, :, off, :].set(kq)
                        pool = pool.at[blk, 1, :, off, :].set(vq)
                        sc = sc.at[blk, 0, :, off].set(ks)
                        sc = sc.at[blk, 1, :, off].set(vs)
                    else:
                        pool = pool.at[blk, 0, :, off, :].set(
                            k.astype(pool.dtype))
                        pool = pool.at[blk, 1, :, off, :].set(
                            v.astype(pool.dtype))
                    kf, vf = gather(pool, bt, sc)
                    S = kf.shape[1]
                    qpos = t[0] + jnp.arange(C)[:, None]
                    kpos = jnp.arange(S)[None, :]
                    mask = jnp.where(kpos <= qpos, 0.0,
                                     -1e30).astype(jnp.float32)
                    att = _sdpa_jnp(q, kf, vf, mask, 0.0, False, None)
                    x = close_layer(li, resid, att, w)
                    new_pools.append(pool)
                    if quantized:
                        new_scales.append(sc)
                return x, new_pools, new_scales
            return body

        # kind == "decode": the PagedLayerCache step (L == 1 plain
        # decode; L > 1 the multi-token verify with the L axis folded
        # into the batch axis — the bit-identity fold)
        B, L = meta["B"], meta["L"]

        def body(pools, scales, W, ops):
            x = ops["x"]
            t, bt = ops["t"], ops["bt"]
            s = jax.lax.axis_index("mp")
            if L == 1:
                blk = jnp.take_along_axis(bt, (t // bs)[:, None],
                                          axis=1)[:, 0]
                off = t % bs
            else:
                pos = (t[:, None]
                       + jnp.arange(L, dtype=t.dtype)[None, :])
                blk = jnp.take_along_axis(bt, pos // bs, axis=1)
                off = pos % bs
            new_pools, new_scales = [], []
            for li in range(nl):
                pool = pools[li]
                sc = scales[li] if quantized else None
                w = W[li]
                resid = x
                h = _ln(x, w.get("ln_w"), w.get("ln_b"),
                        ln_eps[li]) if normalize_before else x
                q, k, v = qkv(h, w, s)
                if quantized:
                    kq, ks = _quant_rows(k[:, 0] if L == 1 else k)
                    vq, vs = _quant_rows(v[:, 0] if L == 1 else v)
                    pool = pool.at[blk, 0, :, off, :].set(kq)
                    pool = pool.at[blk, 1, :, off, :].set(vq)
                    sc = sc.at[blk, 0, :, off].set(ks)
                    sc = sc.at[blk, 1, :, off].set(vs)
                else:
                    pool = pool.at[blk, 0, :, off, :].set(
                        (k[:, 0] if L == 1 else k).astype(pool.dtype))
                    pool = pool.at[blk, 1, :, off, :].set(
                        (v[:, 0] if L == 1 else v).astype(pool.dtype))
                kf, vf = gather(pool, bt, sc)
                S = kf.shape[1]
                kpos = jnp.arange(S)[None, None, None, :]
                if L == 1:
                    qpos = (t[:, None, None, None]
                            + jnp.arange(1)[None, None, :, None])
                    mask = jnp.where(kpos <= qpos, 0.0,
                                     -1e30).astype(jnp.float32)
                    att = _sdpa_jnp(q, kf, vf, mask, 0.0, False,
                                    None)
                else:
                    qd = q.reshape((B * L, 1) + q.shape[2:])
                    kff = jnp.repeat(kf, L, axis=0)
                    vff = jnp.repeat(vf, L, axis=0)
                    tf = (jnp.repeat(t, L)
                          + jnp.tile(jnp.arange(L, dtype=t.dtype), B))
                    qpos = tf[:, None, None, None]
                    mask = jnp.where(kpos <= qpos, 0.0,
                                     -1e30).astype(jnp.float32)
                    att = _sdpa_jnp(qd, kff, vff, mask, 0.0, False,
                                    None)
                    att = att.reshape((B, L) + att.shape[2:])
                x = close_layer(li, resid, att, w)
                new_pools.append(pool)
                if quantized:
                    new_scales.append(sc)
            return x, new_pools, new_scales
        return body
