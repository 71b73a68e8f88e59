"""MoE decode serving: routed expert FFN behind the FusedMultiTransformer
cache protocol.

``MoeServingCore`` subclasses FusedMultiTransformer and overrides exactly
one seam — ``_ffn_block`` — so the attention schedule, the three cache
branches (dense preallocated / paged decode / ragged packed prefill) and
every page/snapshot/journal invariant are inherited unchanged. A MoE
``TokenServingModel`` therefore drops into every engine mode (paged,
prefix-cached, speculative, chunked-prefill, recoverable, tenant-quota'd)
by construction: the engines only ever see the cache protocol.

Per layer the FFN becomes (GShard token-choice routing, ref
incubate/moe.py MoELayer and arxiv 2006.16668):

    gate logits -> softmax -> top-k -> capacity-position assignment
    -> dispatch to experts -> grouped expert FFN -> weighted combine

Two dispatch paths compute the same function:

* **CPU reference** (default off-TPU): a per-expert einsum loop. Every
  expert runs over all N rows and the result is multiplied by the
  capacity-respecting combine weight column — an EXACT zero for every
  (token, expert) pair that is not routed or that overflowed capacity.
  The output is a left-fold ``out += y_e * w[:, e]`` in ascending expert
  order: static shapes, no data-dependent gathers, bit-reproducible.
* **kernel path** (TPU, or ``use_kernel=True`` anywhere for parity
  testing): tokens are scattered into a static capacity layout
  ``[E * cap_pad, d]`` (expert e's rows live at ``e * cap_pad + pos``)
  and the expert FFN runs as two ``ops.pallas.grouped_gemm.gmm`` calls
  over expert-stacked weights ``[E, ...]``. The combine gathers each
  token's k rows back and folds them in ascending expert order so the
  summation association matches the reference fold exactly. The layout
  is fully static (capacity positions, not sorted prefix offsets), which
  is the shape the compiled-step path (inference/compiled_step.py) needs
  to lower dispatch/combine to all-to-all inside its one-program-per-step
  shard_map (GSPMD, arxiv 2105.04663; collective sequences for array
  redistribution, arxiv 2112.01075).

**Capacity overflow = residual bypass.** ``cap = max(int(cf * N * k / E),
k)`` per forward call (N is that call's row count — a ragged packed
prefill step routes with the capacity of its packed row total). A token
slot whose capacity position lands at or past ``cap`` keeps combine
weight 0, so the expert contribution is an exact zero and the token rides
the residual stream through the layer unchanged — deterministic shedding
to identity, never an error. Engines feed full fixed-batch rows including
zero rows for inactive slots; those rows route deterministically (uniform
softmax -> expert 0 by top-k tie order) and consume capacity like any
other row, which is why per-expert load counts include them.

**Expert parallelism.** ``shard_experts(ep)`` partitions the stacked
expert weights over ``parallel.mesh.serving_shard_devices(ep)`` —
contiguous expert ranges per shard, host-staged loop exactly like the
PR 15/17 ``mp`` serving shards. Because non-routed contributions are
exact zeros, the combine is a disjoint sum: the fold walks shards in
expert order with ONE running accumulator, so the sequence of additions
(and therefore every bit of the output) is identical to the unsharded
fold. Gate and attention stay replicated.

Per-expert load / overflow accumulate as device-side arrays on the hot
path (no host sync); ``moe_metrics()`` is the cold scrape the engine
attaches to its MetricsRegistry under the ``moe.*`` namespace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import device as _device
from ..framework.op import apply, unwrap
from ..framework.tensor import Parameter
from .. import nn
from ..incubate.nn.fused_transformer import FusedMultiTransformer
from ..ops.pallas.grouped_gemm import gmm


def moe_capacity(capacity_factor, n_tokens, top_k, num_experts):
    """Per-call expert capacity (GShard): ``max(int(cf*N*k/E), k)``."""
    return max(int(capacity_factor * n_tokens * top_k / num_experts), top_k)


def _act_fn(name):
    # F.gelu is exact erf; jax.nn.gelu defaults to tanh-approximate.
    if name == "gelu":
        return lambda h: jax.nn.gelu(h, approximate=False)
    return getattr(jax.nn, name)


def _route_impl(lg, k, E, cap):
    """GShard top-k routing with capacity positions (incubate/moe.py
    ``_gshard_routing``), flattened for the serving dispatch paths.

    Returns ``(w, expert, pos, keep, val, load, dropped)``:
      w       [N, E] capacity-respecting combine weights (exact 0 for
              non-routed and overflowed pairs — the residual-bypass mask)
      expert  [k, N] int32 expert id per top-k slot
      pos     [k, N] int32 capacity position within the expert
      keep    [k, N] bool, pos < cap
      val     [k, N] combine weight per slot (0 where dropped)
      load    [E] int32 kept assignments per expert (this call)
      dropped [E] int32 overflowed assignments per expert (this call)
    """
    probs = jax.nn.softmax(lg, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, -1, keepdims=True)

    offset = jnp.zeros((E,), jnp.int32)
    w = jnp.zeros(lg.shape, lg.dtype)
    load = jnp.zeros((E,), jnp.int32)
    dropped = jnp.zeros((E,), jnp.int32)
    es, ps, ks, vs = [], [], [], []
    for slot in range(k):
        idx = topi[:, slot]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
        pos = jnp.sum(((jnp.cumsum(onehot, axis=0) - 1)
                       + offset[None, :]) * onehot, -1)
        keep = pos < cap
        val = jnp.where(keep, topv[:, slot], 0.0)
        w = w + onehot.astype(lg.dtype) * val[:, None]
        kept_oh = onehot * keep[:, None].astype(jnp.int32)
        load = load + jnp.sum(kept_oh, axis=0)
        dropped = dropped + jnp.sum(onehot - kept_oh, axis=0)
        es.append(idx.astype(jnp.int32))
        ps.append(pos.astype(jnp.int32))
        ks.append(keep)
        vs.append(val)
        offset = offset + jnp.sum(onehot, axis=0)
    return (w, jnp.stack(es), jnp.stack(ps), jnp.stack(ks), jnp.stack(vs),
            load, dropped)


def _expert_contrib_impl(x, we, w1, b1, w2, b2, act):
    """One expert's weighted residual contribution: ``act(x@w1+b1)@w2+b2``
    scaled by the combine-weight column (exact 0 for non-routed rows).

    This single impl is the unit of bit-reproducibility: the unsharded
    fold and every ``shard_experts`` shard run the SAME code object with
    the SAME shapes, so the eager-op jit cache hands back the same
    executable and the contributions are bitwise identical wherever the
    expert weights live.
    """
    h = _act_fn(act)(x @ w1 + b1)
    y = h @ w2 + b2
    return y * we[:, None]


def _grouped_ffn_impl(x, expert, pos, keep, val, w1, b1, w2, b2,
                      E, cap_pad, block_m, act):
    """Kernel-path dispatch/combine around two grouped GEMMs.

    Static capacity layout: row ``e * cap_pad + pos`` holds the token
    assigned to expert ``e`` at capacity position ``pos``; overflowed
    slots scatter out of bounds and are dropped. ``cap_pad`` is the
    capacity rounded up to ``block_m`` so every gmm m-block belongs to
    exactly one expert. Unfilled rows compute garbage through the FFN
    and are never gathered back. The combine folds each token's k slot
    contributions in ascending EXPERT order — the same summation
    association as the reference per-expert fold, so both paths agree
    bit-for-bit at dims where the row-wise GEMM is row-count invariant.
    """
    k, n = expert.shape
    rows = E * cap_pad
    row_e = jnp.repeat(jnp.arange(E, dtype=jnp.int32), cap_pad)
    block_expert = jnp.repeat(jnp.arange(E, dtype=jnp.int32),
                              cap_pad // block_m)
    plhs = jnp.zeros((rows, x.shape[1]), x.dtype)
    for slot in range(k):
        ridx = jnp.where(keep[slot], expert[slot] * cap_pad + pos[slot],
                         rows)
        plhs = plhs.at[ridx].set(x, mode="drop")
    h = gmm(plhs, w1, block_expert, block_m=block_m) + b1[row_e]
    h = _act_fn(act)(h)
    y = gmm(h, w2, block_expert, block_m=block_m) + b2[row_e]

    contribs = []
    for slot in range(k):
        ridx = jnp.clip(expert[slot] * cap_pad + pos[slot], 0, rows - 1)
        contribs.append(y[ridx] * val[slot][:, None])
    g = jnp.stack(contribs)                       # [k, N, d]
    order = jnp.argsort(expert, axis=0)           # slots by expert id
    out = jnp.zeros_like(x)
    for j in range(k):
        out = out + jnp.take_along_axis(g, order[j][None, :, None],
                                        axis=0)[0]
    return out


class MoeServingCore(FusedMultiTransformer):
    """Token-choice MoE decoder stack speaking the serving cache protocol.

    Construction replaces each block's dense ``ffn1``/``ffn2`` with a
    router (``blk.gate``) and expert-stacked parameters ``moe_w1 [E,d,f]``,
    ``moe_b1 [E,f]``, ``moe_w2 [E,f,d]``, ``moe_b2 [E,d]`` — drawn as E
    independent Xavier Linears (deterministic under ``paddle.seed``) and
    stacked, so ``shard_experts`` can partition axis 0 over devices.

    ``use_kernel``: None = grouped-GEMM path on TPU, per-expert einsum
    reference elsewhere; True/False force a path (True on CPU runs the
    gmm interpret kernel — the parity-test configuration).
    """

    def __init__(self, embed_dim, num_heads, dim_feedforward,
                 num_experts=4, top_k=2, capacity_factor=1.25,
                 dropout_rate=0.0, activation="gelu", normalize_before=True,
                 epsilon=1e-5, num_layers=1, use_kernel=None, block_m=8):
        if num_experts < top_k:
            raise ValueError(f"num_experts={num_experts} < top_k={top_k}")
        super().__init__(embed_dim, num_heads, dim_feedforward,
                         dropout_rate=dropout_rate, activation=activation,
                         normalize_before=normalize_before, epsilon=epsilon,
                         num_layers=num_layers)
        self.num_experts = int(num_experts)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.moe_ffn_dim = int(dim_feedforward)
        self._use_kernel = use_kernel
        self._block_m = int(block_m)
        for blk in self.layers:
            # expert weights drawn as E independent Linears so the init
            # distribution matches a dense ffn1/ffn2 per expert, then
            # stacked on a leading expert axis for sharding/gmm
            fc1 = [nn.Linear(embed_dim, dim_feedforward)
                   for _ in range(num_experts)]
            fc2 = [nn.Linear(dim_feedforward, embed_dim)
                   for _ in range(num_experts)]
            del blk.ffn1
            del blk.ffn2
            blk.gate = nn.Linear(embed_dim, num_experts)
            blk.moe_w1 = Parameter(jnp.stack([unwrap(l.weight) for l in fc1]))
            blk.moe_b1 = Parameter(jnp.stack([unwrap(l.bias) for l in fc1]))
            blk.moe_w2 = Parameter(jnp.stack([unwrap(l.weight) for l in fc2]))
            blk.moe_b2 = Parameter(jnp.stack([unwrap(l.bias) for l in fc2]))
        self._ep = None
        self._ep_devices = None
        self._ep_weights = None
        self._calls = 0
        self._rows = 0
        self._load = [jnp.zeros((self.num_experts,), jnp.int32)
                      for _ in range(self.num_layers)]
        self._dropped = [jnp.zeros((self.num_experts,), jnp.int32)
                         for _ in range(self.num_layers)]
        # Per-op eager, never whole-forward capture: the forward is
        # side-effectful by design (device-side load/overflow
        # accumulators above), and — more load-bearing — the per-expert
        # combine fold must execute as a sequence of standalone cached
        # executables so the unsharded and shard_experts dispatches run
        # the SAME programs on the same shapes. A whole-forward capture
        # would hand each layout to XLA as one differently-fusable
        # program and void the bitwise ep-equivalence contract.
        from ..framework import layer_jit
        layer_jit.mark_unsafe(self)

    # ---- configuration surface --------------------------------------

    @property
    def moe_spec(self):
        """Static routing spec — the WorkModel pricing hook."""
        return {"num_experts": self.num_experts, "top_k": self.top_k,
                "capacity_factor": self.capacity_factor,
                "ffn_dim": self.moe_ffn_dim}

    def _kernel_on(self):
        if self._use_kernel is None:
            return _device.use_pallas_kernels()
        return bool(self._use_kernel)

    def shard_experts(self, ep, devices=None):
        """Partition the expert-stacked weights over ``ep`` shards.

        Contiguous expert ranges per shard, device-resident via
        ``serving_shard_devices`` (LOGICAL shards on repeated devices when
        the platform has fewer — the host-staged loop does not care).
        Returns self; dispatch switches to the shard loop in
        ``_combine_fold``. The kernel path stays single-program — with
        distinct devices the compiled-step lowering would express the
        dispatch/combine as all-to-all instead (see module docstring).
        """
        from ..parallel.mesh import serving_shard_devices
        ep = int(ep)
        if ep < 1 or self.num_experts % ep:
            raise ValueError(
                f"ep={ep} must divide num_experts={self.num_experts}")
        devs = list(devices) if devices is not None \
            else serving_shard_devices(ep)[:ep]
        per = self.num_experts // ep
        weights = []
        for blk in self.layers:
            shards = []
            for s in range(ep):
                lo = s * per
                sl = tuple(jax.device_put(unwrap(p)[lo:lo + per], devs[s])
                           for p in (blk.moe_w1, blk.moe_b1,
                                     blk.moe_w2, blk.moe_b2))
                shards.append(sl)
            weights.append(shards)
        self._ep = ep
        self._ep_devices = devs
        self._ep_weights = weights
        return self

    def truncated(self, num_layers):
        """First-``num_layers`` weight-SHARING twin — the MoE analogue of
        the dense truncated draft (speculative.TokenServingModel)."""
        if not (0 < num_layers <= self.num_layers):
            raise ValueError(f"num_layers must be in [1, {self.num_layers}]")
        clone = MoeServingCore(
            self.embed_dim, self.num_heads, self.moe_ffn_dim,
            num_experts=self.num_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            activation=self._act_name,
            normalize_before=self.normalize_before,
            num_layers=num_layers, use_kernel=self._use_kernel,
            block_m=self._block_m)
        clone.layers = nn.LayerList(
            [self.layers[i] for i in range(num_layers)])
        return clone

    # ---- metrics ----------------------------------------------------

    def moe_metrics(self):
        """Cold scrape for MetricsRegistry.attach("moe", ...): pulls the
        device-side per-expert accumulators to host. Flattens to
        ``moe.load.<e>``, ``moe.overflow.<e>``, ``moe.routed_tokens``,
        ``moe.dropped_tokens``, ``moe.overflow_rate`` ... — the signal
        catalog the expert-collapse detector samples."""
        load = np.zeros((self.num_experts,), np.int64)
        drop = np.zeros((self.num_experts,), np.int64)
        for i in range(self.num_layers):
            load += np.asarray(self._load[i]).astype(np.int64)
            drop += np.asarray(self._dropped[i]).astype(np.int64)
        routed = int(load.sum())
        dropped = int(drop.sum())
        total = routed + dropped
        return {
            "experts": self.num_experts,
            "top_k": self.top_k,
            "ep": self._ep or 0,
            "calls": self._calls,
            "rows": self._rows,
            "routed_tokens": routed,
            "dropped_tokens": dropped,
            "overflow_rate": (dropped / total) if total else 0.0,
            "load": {str(e): int(load[e]) for e in range(self.num_experts)},
            "overflow": {str(e): int(drop[e])
                         for e in range(self.num_experts)},
        }

    # ---- snapshot / restore -----------------------------------------

    def snapshot(self):
        """Routing config + per-expert counters (JSON-clean). Weights ride
        state_dict() like any Layer; this is the serving-side state."""
        return {
            "kind": "moe_serving_core",
            "config": {
                "num_experts": self.num_experts,
                "top_k": self.top_k,
                "capacity_factor": self.capacity_factor,
                "ffn_dim": self.moe_ffn_dim,
                "block_m": self._block_m,
                "use_kernel": self._use_kernel,
                "ep": self._ep,
            },
            "counters": {
                "calls": self._calls,
                "rows": self._rows,
                "load": [[int(v) for v in np.asarray(a)]
                         for a in self._load],
                "overflow": [[int(v) for v in np.asarray(a)]
                             for a in self._dropped],
            },
        }

    def restore(self, snap):
        cfg = snap["config"]
        if (cfg["num_experts"] != self.num_experts
                or cfg["top_k"] != self.top_k
                or cfg["capacity_factor"] != self.capacity_factor
                or cfg["ffn_dim"] != self.moe_ffn_dim
                or cfg["block_m"] != self._block_m):
            raise ValueError("snapshot routing config mismatch")
        self._use_kernel = cfg["use_kernel"]
        if cfg["ep"] and cfg["ep"] != self._ep:
            self.shard_experts(cfg["ep"])
        cnt = snap["counters"]
        self._calls = int(cnt["calls"])
        self._rows = int(cnt["rows"])
        self._load = [jnp.asarray(v, jnp.int32) for v in cnt["load"]]
        self._dropped = [jnp.asarray(v, jnp.int32)
                         for v in cnt["overflow"]]

    # ---- dispatch ---------------------------------------------------

    def _ffn_block(self, i, blk, x):
        residual = x
        h = blk.ffn_ln(x) if self.normalize_before else x
        h = self._moe_ffn(i, blk, h)
        x = residual + h
        if not self.normalize_before:
            x = blk.ffn_ln(x)
        return x

    def _moe_ffn(self, i, blk, h):
        from ..ops.manipulation import reshape
        shape = h.shape
        x2 = reshape(h, [-1, shape[-1]])
        n = x2.shape[0]
        cap = moe_capacity(self.capacity_factor, n, self.top_k,
                           self.num_experts)
        logits = blk.gate(x2)
        w, expert, pos, keep, val, load, dropped = apply(
            _route_impl, (logits,),
            {"k": self.top_k, "E": self.num_experts, "cap": cap},
            differentiable=False, op_name="moe_route")
        # device-side accumulate (raw arrays — no host sync, no tape).
        # Skipped inside a foreign trace (some outer layer capturing
        # through us): storing a tracer would poison the accumulators;
        # our own capture is already opted out in __init__.
        raw_load = unwrap(load)
        if not isinstance(raw_load, jax.core.Tracer):
            self._load[i] = self._load[i] + raw_load
            self._dropped[i] = self._dropped[i] + unwrap(dropped)
            if i == 0:
                self._calls += 1
                self._rows += n
        if self._ep is not None:
            out = self._combine_fold(i, blk, x2, w)
        elif self._kernel_on():
            cap_pad = -(-cap // self._block_m) * self._block_m
            out = apply(
                _grouped_ffn_impl,
                (x2, expert, pos, keep, val,
                 blk.moe_w1, blk.moe_b1, blk.moe_w2, blk.moe_b2),
                {"E": self.num_experts, "cap_pad": cap_pad,
                 "block_m": self._block_m, "act": self._act_name},
                differentiable=False, op_name="moe_grouped_ffn")
        else:
            out = self._combine_fold(i, blk, x2, w)
        return reshape(out, shape)

    def _combine_fold(self, i, blk, x2, w):
        """Reference combine: left-fold of per-expert contributions in
        ascending expert order. One running accumulator walks every
        expert — sharded or not — so the addition sequence is identical
        for any ``ep`` (non-routed contributions are exact zeros; the
        zero-padded disjoint-sum discipline of the PR 15 combine)."""
        act = self._act_name
        out = None
        if self._ep is None:
            groups = [((blk.moe_w1, blk.moe_b1, blk.moe_w2, blk.moe_b2),
                       0, None)]
        else:
            per = self.num_experts // self._ep
            groups = [(self._ep_weights[i][s], s * per,
                       self._ep_devices[s]) for s in range(self._ep)]
        for (w1, b1, w2, b2), lo, dev in groups:
            xs = x2 if dev is None else jax.device_put(unwrap(x2), dev)
            ws = w if dev is None else jax.device_put(unwrap(w), dev)
            local_e = w1.shape[0]
            for e in range(local_e):
                contrib = apply(
                    _expert_contrib_impl,
                    (xs, ws[:, lo + e], w1[e], b1[e], w2[e], b2[e]),
                    {"act": act},
                    differentiable=False, op_name="moe_expert_contrib")
                if dev is not None:
                    contrib = jax.device_put(unwrap(contrib),
                                             self._ep_devices[0])
                out = contrib if out is None else out + contrib
        return out


# ---------------------------------------------------------------------
# Dropless sigmoid routing with a selection bias and a shared expert:
# the expert layer of inference/decoder.py (pure functions of arrays).
# ---------------------------------------------------------------------

# weight columns a grouped-GEMM step of the dropless layer streams (with
# the whole of K: 3 MB of bf16 at a width of 3072, read once an expert)
GMM_BLOCK_N = 512


def expert_row_block(rows: int, top_k: int, num_experts: int) -> int:
    """Rows an m-block of the grouped GEMM carries, from the call's row
    count: half again what an expert expects (rows * top_k / experts),
    rounded up to a power of two, between one bf16 sublane tile and
    128. A 32-row decode step gets 16, a 2 080-row mixed step 64, so a
    group is one block almost always and the padding stays small."""
    want = max(1, -(-3 * rows * top_k // (2 * num_experts)))
    return min(128, max(16, 1 << (want - 1).bit_length()))


def sigmoid_route(m, router, bias, top_k: int, route_norm: bool,
                  route_scale: float, norm_eps: float = 1e-20):
    """Scores over ALL experts in float32: ``s = sigmoid(m @ router)``,
    choose ``top_k`` of ``s + bias`` (the bias picks, it never weighs),
    weigh with ``s`` normalised over the chosen plus ``norm_eps``, the
    architecture's own (``route_norm``), times ``route_scale``. Returns
    (expert ids [R, k] int32, weights [R, k] float32, scores [R, E]
    float32)."""
    logits = jnp.dot(m, router, preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias[None, :], top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + norm_eps)
    return idx.astype(jnp.int32), w * route_scale, s


def local_groups(idx, expert_offset: int, experts_held: int,
                 block_m: int):
    """Lay the assignments that fall on this chip's experts
    ``[expert_offset, expert_offset + experts_held)`` out for the
    grouped GEMM, without dropping any: expert e's rows start at a
    ``block_m`` multiple, in arrival order. Static shapes from the
    worst case (every assignment local).

    Returns ``dest`` [R, k] int32 (the layout row of each assignment;
    ``rows_pad`` where it is another chip's), ``block_expert``
    [rows_pad // block_m], ``blocks_used`` [1], ``counts``
    [experts_held] (rows each held expert received) and ``rows_pad``."""
    R, k = idx.shape
    rows_pad = -(-(R * k + experts_held * (block_m - 1)) // block_m) \
        * block_m
    local = idx - expert_offset
    here = (local >= 0) & (local < experts_held)
    flat = jnp.where(here, local, experts_held).reshape(-1)     # [R*k]
    onehot = (flat[:, None] == jnp.arange(experts_held)[None, :]) \
        .astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, -1)
    counts = jnp.sum(onehot, axis=0)
    padded = -(-counts // block_m) * block_m
    ends = jnp.cumsum(padded)
    starts = ends - padded
    dest = jnp.where(here.reshape(-1),
                     starts[jnp.minimum(flat, experts_held - 1)] + rank,
                     rows_pad).reshape(R, k)
    blocks_used = ends[-1:] // block_m
    nb = rows_pad // block_m
    # block b belongs to the first expert whose padded range ends past
    # it; the unused tail repeats the last real block's expert
    b = jnp.minimum(jnp.arange(nb), jnp.maximum(blocks_used[0] - 1, 0))
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, b * block_m, side="right"),
        experts_held - 1)
    return dest, block_expert.astype(jnp.int32), \
        blocks_used.astype(jnp.int32), counts, rows_pad


def swiglu(x, w_gate_up, w_down):
    """``(silu(x Wg) * (x Wu)) Wd`` with gate|up fused on the output
    axis; bf16 (or whatever ``x`` is) between the products, float32
    accumulation inside them."""
    gu = jnp.dot(x, w_gate_up,
                 preferred_element_type=jnp.float32).astype(x.dtype)
    g, u = jnp.split(gu, 2, axis=-1)
    h = (jax.nn.silu(g.astype(jnp.float32))
         * u.astype(jnp.float32)).astype(x.dtype)
    return jnp.dot(h, w_down, preferred_element_type=jnp.float32)


def dropless_experts(m, idx, w, we_gate_up, we_down, expert_offset: int,
                     block_m: int):
    """This chip's part of ``sum_e w_e expert_e(m)``: group the local
    assignments by expert, two grouped GEMMs (gate|up fused, then
    down), gather each row's results back and weigh them in float32.
    No capacity, nothing dropped; what the other chips' experts would
    add is not here. Returns ([R, d] float32, counts [experts_held])."""
    R, k = idx.shape
    held = we_gate_up.shape[0]
    dest, block_expert, used, counts, rows_pad = local_groups(
        idx, expert_offset, held, block_m)
    lhs = jnp.zeros((rows_pad, m.shape[-1]), m.dtype)
    lhs = lhs.at[dest.reshape(-1)].set(jnp.repeat(m, k, axis=0),
                                       mode="drop")

    def product(x, weights):      # the whole of K a step (``gmm``)
        return gmm(x, weights, block_expert, block_m=block_m,
                   block_n=GMM_BLOCK_N, block_k=x.shape[-1],
                   blocks_used=used)
    gu = product(lhs, we_gate_up)
    g, u = jnp.split(gu, 2, axis=-1)
    h = (jax.nn.silu(g.astype(jnp.float32))
         * u.astype(jnp.float32)).astype(m.dtype)
    y = product(h, we_down)
    here = dest < rows_pad
    rows = y[jnp.minimum(dest, rows_pad - 1)].astype(jnp.float32)
    # where, not a product with zero: rows past the used blocks hold
    # whatever the buffer held
    out = jnp.sum(jnp.where(here[..., None], rows * w[..., None], 0.0),
                  axis=1)
    return out, counts
