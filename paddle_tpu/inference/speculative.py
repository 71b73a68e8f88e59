"""Speculative decoding over the paged serving engine, behind a
token-ID serving surface.

The paged KV cache made rejection CHEAP: a speculative tail that the
target model refuses is a block-table truncation
(``PagedKVCache.truncate``) — pages fall off the tail, shared pages
just deref, and nothing is copied. This module adds the two layers the
ROADMAP names on top of that:

* ``TokenServingModel`` — the token-ID serving surface. The engines
  underneath speak embeddings; this wrapper owns the embedding table
  and the readout head, so the serving API is token ids in, logits
  out, with greedy / temperature / top-k sampling computed on-device.

* ``SpeculativeEngine`` — draft / verify / rollback. Per step it
  (1) rolls a small DRAFT model K tokens ahead through its own
  (second, smaller) paged cache, (2) verifies all K+1 positions in ONE
  target-model call (``PagedServingEngine.step_multi`` — the ragged
  multi-token attention shape the multi-query paged kernel serves on
  TPU), (3) accepts the longest agreeing prefix by standard
  (rejection-sampling) acceptance, and (4) rolls the rejected tail
  back page-wise (``PagedServingEngine.rollback``). ``k=0`` degrades
  to plain (non-speculative) token-ID paged serving — the baseline the
  bench compares against.

Greedy bit-identity: with ``sampling="greedy"`` the emitted stream is
BIT-IDENTICAL to non-speculative paged decode, whatever the draft
proposes. Every emitted token is an argmax over TARGET logits; the
multi-query verification computes each position's hidden with the same
masked full-extent reductions as the one-token step, and per-row
matmul results on this backend are invariant to the number of rows
ridden in the call (the l==1 GEMV caveat of
scheduler.MIN_PREFILL_SUFFIX_ROWS is about 1-ROW calls, which the
verify path never makes: it rides max_batch*(K+1) rows). Asserted in
tests/test_speculative.py, including across mid-stream rejection
rollbacks, preempt -> re-prefill, and prefix caching.

Scheduling composition: the target path IS a ``PagedServingEngine`` —
admission, block-budget watermark, preemption with re-prefill from
(accepted-only) history, and cross-request prefix caching all apply
unchanged. The draft cache is slot-for-slot aligned with the target's
and is sized to never be the bottleneck (it is fully reservable:
``max_batch * max_blocks_per_seq + 1`` blocks by default — cheap,
because the draft model is small); on a target preemption the draft
slot is dropped and re-prefilled from the token stream at
re-admission.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..framework.autograd import no_grad
from ..framework.tensor import Tensor
from .paged_cache import BlockOOM, PagedKVCache
from .resilience import RequestOutcome
from .scheduler import PagedServingEngine, chunked_prefill
from .serving import SpecDecodeStats

__all__ = ["TokenServingModel", "SpeculativeEngine", "SpecDecodeStats",
           "branch_lane_seed", "register_logit_mask", "logit_mask_fn"]


def branch_lane_seed(seed: int, branch: int) -> int:
    """Deterministic RNG-lane seed for branch ``branch`` of a group
    submitted with ``seed``: branch 0 IS the request seed (a lone
    seeded request and a group lead draw identically), later branches
    decorrelate through the golden-ratio increment. This derivation is
    the published bit-identity oracle: an n-branch group's streams are
    byte-for-byte the streams of n independent submits seeded
    ``branch_lane_seed(seed, i)`` for i in range(n)."""
    return (int(seed) + 0x9E3779B9 * int(branch)) % (2 ** 32)


# -- grammar / JSON constrained decoding: the logit-mask registry -----
# Masks register BY NAME so snapshots and recovery journals carry a
# string, not a callable — replay re-resolves the name. A mask fn maps
# (tokens_so_far, vocab_size) -> bool[vocab_size], True where the
# grammar allows the next token; it must allow at least one token.
_LOGIT_MASKS: Dict[str, object] = {}


def register_logit_mask(name: str, fn) -> None:
    """Register ``fn(tokens_so_far: List[int], vocab_size: int) ->
    bool[vocab_size]`` under ``name``. Sampling applies the mask
    additively (0 where allowed, -1e30 where banned) BEFORE softmax /
    argmax on every lane that carries it — draft proposals, target
    verification and the rejection-sampling residual all stay inside
    the language, at zero kernel cost (the mask rides the logits into
    the existing ops)."""
    if not callable(fn):
        raise ValueError("logit mask must be callable")
    _LOGIT_MASKS[str(name)] = fn


def logit_mask_fn(name: str):
    """Resolve a registered mask by name (KeyError names the miss)."""
    try:
        return _LOGIT_MASKS[name]
    except KeyError:
        raise KeyError(
            f"unknown logit mask {name!r} — register_logit_mask() it "
            f"before submit") from None


def _readout(hidden, head, *gain, eps):
    import jax
    import jax.numpy as jnp
    h = hidden.astype(jnp.float32)
    if gain:
        h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps) \
            * gain[0]
    return jnp.dot(h.astype(head.dtype), head,
                   preferred_element_type=jnp.float32)


def apply_readout(hidden, head, gain, eps) -> Tensor:
    """``RMSNorm(hidden) @ head`` (the norm only where ``gain`` is
    given): statistics in float32, the product in the head's stored
    type with float32 accumulation, logits float32."""
    from ..framework.op import apply
    args = (hidden, head) + (() if gain is None else (gain,))
    return apply(_readout, args, {"eps": float(eps)},
                 differentiable=False, op_name="readout")


class TokenServingModel:
    """Token-ID serving surface over a FusedMultiTransformer-protocol
    core: owns the embedding table ([vocab, d_model]) and the readout
    head ([d_model, vocab], tied to the embedding transpose when not
    given), so callers speak token ids while the serving engines keep
    speaking embeddings. ``logits``/``sample`` run on-device (matmul /
    softmax / argmax / top-k masking); only the final categorical draw
    (and the probability rows rejection sampling needs) come to
    host."""

    def __init__(self, model, embedding, lm_head=None,
                 weight_dtype: str = "float32", final_norm=None,
                 norm_eps: float = 1e-5, input_scale: float = 1.0):
        import jax.numpy as jnp
        self.core = model
        # ``input_scale`` multiplies the looked-up rows (an embedding
        # multiplier such as sqrt(d_model)); ``final_norm`` ([d_model]
        # gains) puts an RMSNorm before the readout. Both default to
        # what the GPT-3 block serves with: neither.
        self.input_scale = float(input_scale)
        self.final_norm = None if final_norm is None else \
            jnp.asarray(final_norm, jnp.float32)
        self.norm_eps = float(norm_eps)
        emb = np.asarray(embedding.numpy() if hasattr(embedding, "numpy")
                         else embedding, np.float32)
        if emb.ndim != 2:
            raise ValueError("embedding must be [vocab, d_model]")
        self._embed_np = emb
        head_shape = (emb.shape[1], emb.shape[0])
        if lm_head is None:
            self.lm_head = Tensor(jnp.asarray(emb.T.copy()))  # tied
        elif isinstance(lm_head, Tensor):
            # share the device buffer (truncated_draft hands the
            # target's own head over — no host round-trip, no copy)
            if tuple(lm_head.shape) != head_shape:
                raise ValueError(f"lm_head must be [d_model, vocab] = "
                                 f"{head_shape}, got {lm_head.shape}")
            self.lm_head = lm_head
        else:
            head = np.asarray(lm_head, np.float32)
            if head.shape != head_shape:
                raise ValueError(f"lm_head must be [d_model, vocab] = "
                                 f"{head_shape}, got {head.shape}")
            self.lm_head = Tensor(jnp.asarray(head))
        # opt-in INT8 WEIGHT path (weight_dtype="int8"): the readout
        # projection — the one weight this serving surface owns, and
        # at vocab x d_model typically the largest single serving
        # matrix — is stored int8 with per-OUTPUT-CHANNEL (per-vocab-
        # column) symmetric scales. The matmul streams the int8 weight
        # (ops/pallas/int8_matmul.w8a16_matmul on TPU; a dequantizing
        # XLA contraction as the CPU/odd-shape fallback) and the scale
        # multiply folds into the readout epilogue — ~2x weight HBM
        # vs bf16 (4x vs f32) on the weight-bound decode readout.
        # Off by default: float32 readout is bit-identical to before.
        if weight_dtype not in ("float32", "int8", "bfloat16"):
            raise ValueError(f"unsupported weight_dtype "
                             f"{weight_dtype!r} (float32 | int8 | "
                             f"bfloat16)")
        self.weight_dtype = weight_dtype
        if weight_dtype == "bfloat16" and \
                self.lm_head.data.dtype != jnp.bfloat16:
            # stored bf16, multiplied with float32 accumulation
            self.lm_head = Tensor(self.lm_head.data.astype(jnp.bfloat16))
        self._head_int8: Optional[Tensor] = None
        self._head_scale: Optional[Tensor] = None
        if weight_dtype == "int8":
            # quantization.functional convention: scale is the
            # per-channel amax, qmax folded inside quantized_matmul
            w = np.asarray(self.lm_head.numpy(), np.float32)
            amax = np.abs(w).max(axis=0)             # per out-channel
            q = np.clip(np.round(w * (127.0 / np.maximum(amax, 1e-30))
                                 [None]), -127, 127).astype(np.int8)
            self._head_int8 = Tensor(jnp.asarray(q))
            self._head_scale = Tensor(jnp.asarray(
                amax.astype(np.float32)))

    def weight_bytes(self) -> int:
        """HBM bytes of the readout head as stored (int8 payload +
        per-channel scales when quantized) — the honest number the
        cost reports cite next to kv_bytes_per_token()."""
        if self._head_int8 is not None:
            return (int(np.prod(self._head_int8.shape))
                    + 4 * int(self._head_scale.shape[0]))
        return int(np.prod(self.lm_head.shape)) \
            * self.lm_head.data.dtype.itemsize

    @property
    def vocab_size(self) -> int:
        return self._embed_np.shape[0]

    @property
    def d_model(self) -> int:
        return self._embed_np.shape[1]

    # -- token <-> embedding ------------------------------------------
    def embed(self, token_ids) -> np.ndarray:
        """Token ids (any int sequence/array) -> float32 embedding rows
        [..., d_model] — the currency the serving engines consume."""
        ids = np.asarray(token_ids, np.int64)
        if ids.size and (ids.min() < 0 or ids.max() >= self.vocab_size):
            raise ValueError("token id out of range")
        rows = self._embed_np[ids]       # a copy: fancy indexing
        if self.input_scale != 1.0:
            rows *= np.float32(self.input_scale)   # in place: one pass
        return rows

    def logits(self, hidden) -> Tensor:
        """hidden [..., d_model] Tensor -> logits [..., vocab] Tensor
        (on-device readout matmul; the int8 weight path streams the
        quantized head and folds the per-channel scale into the
        epilogue — see __init__)."""
        import paddle_tpu as paddle
        if self.final_norm is not None or self.weight_dtype == "bfloat16":
            return apply_readout(hidden, self.lm_head, self.final_norm,
                                 self.norm_eps)
        if self._head_int8 is None:
            return paddle.matmul(hidden, self.lm_head)
        # weight-only int8 GEMM: the w8a16 Pallas kernel behind the
        # FLAGS_enable_pallas_kernels gate, dequantizing XLA
        # contraction at shapes outside the kernel tiling — the ONE
        # implementation quantization/functional.py already owns
        from ..quantization.functional import quantized_matmul
        return quantized_matmul(hidden, self._head_int8,
                                self._head_scale)

    # -- sampling ------------------------------------------------------
    def probs(self, logits, temperature: float = 1.0,
              top_k: Optional[int] = None) -> Tensor:
        """Temperature-scaled, top-k-masked softmax over the last axis,
        computed on-device. The distribution rejection sampling prices
        proposals against."""
        import paddle_tpu as paddle
        from ..nn import functional as F
        z = logits
        if temperature != 1.0:
            if temperature <= 0:
                raise ValueError("temperature must be > 0 (use "
                                 "mode='greedy' for argmax decoding)")
            z = z / temperature
        if top_k is not None and top_k < self.vocab_size:
            kth = paddle.topk(z, k=top_k, axis=-1)[0].min(axis=-1,
                                                          keepdim=True)
            z = paddle.where(z < kth, paddle.full_like(z, -1e30), z)
        return F.softmax(z, axis=-1)

    def sample(self, logits, mode: str = "greedy",
               temperature: float = 1.0, top_k: Optional[int] = None,
               rng: Optional[np.random.RandomState] = None,
               rng_rows: Optional[list] = None,
               logit_mask=None, collector=None
               ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """logits [..., vocab] Tensor -> (token ids int64 [...], probs
        float32 [..., vocab] or None). Greedy is a pure on-device
        argmax (probs None). Stochastic modes build the distribution
        on-device and draw per row on host with ``rng`` (inverse-CDF),
        returning the probs so speculative rejection sampling can
        price the draws.

        ``rng_rows`` (branch groups): one RandomState-or-None per FLAT
        row — a laned row draws its uniform from its own lane,
        laneless rows fall back to ``rng`` sequentially. MT19937's
        batched ``random_sample(n)`` IS n sequential draws, so passing
        None (the default) or all-None rows is bit-identical to the
        batched path.

        ``logit_mask`` (grammar-constrained decoding): bool array of
        the logits' shape, True where the grammar allows the token;
        applied ADDITIVELY (0 allowed / -1e30 banned) before argmax /
        softmax, so greedy picks the best in-language token and the
        stochastic distribution renormalizes over the language — and
        the rejection-sampling residual max(p - q, 0) stays
        in-language because BOTH p and q were masked. None skips the
        add entirely (bit-identical to before).

        ``collector`` (TraceCollector): the one blocking
        device-to-host read below is recorded as ``device_wait`` — the
        host waiting for the device, apart from the host work around
        it."""
        import paddle_tpu as paddle
        if logit_mask is not None:
            neg = np.where(np.asarray(logit_mask, bool), 0.0,
                           -1e30).astype(np.float32)
            logits = logits + paddle.to_tensor(neg)
        if mode == "greedy":
            out = paddle.argmax(logits, axis=-1)
        elif mode in ("sample", "top_k", "temperature"):
            out = self.probs(logits, temperature, top_k)
        else:
            raise ValueError(f"unknown sampling mode {mode!r}")
        if collector is not None:
            collector.span_begin("device_wait")
        try:
            host = np.asarray(out.numpy())
        finally:
            if collector is not None:
                collector.span_end()
        if mode == "greedy":
            return host.astype(np.int64), None
        p = host.astype(np.float32, copy=False)
        if rng is None:
            rng = np.random
        flat = p.reshape(-1, p.shape[-1]).astype(np.float64)
        flat = flat / flat.sum(axis=-1, keepdims=True)
        if rng_rows is None:
            u = rng.random_sample(flat.shape[0])
        else:
            if len(rng_rows) != flat.shape[0]:
                raise ValueError(
                    f"rng_rows needs one entry per flat row "
                    f"({flat.shape[0]}), got {len(rng_rows)}")
            u = np.empty(flat.shape[0], np.float64)
            for i in range(flat.shape[0]):
                r = rng_rows[i]
                u[i] = (rng if r is None else r).random_sample()
        cdf = np.cumsum(flat, axis=-1)
        toks = np.empty(flat.shape[0], np.int64)
        for i in range(flat.shape[0]):
            toks[i] = int(np.searchsorted(cdf[i], u[i], side="right"))
        toks = np.minimum(toks, p.shape[-1] - 1)
        return toks.reshape(p.shape[:-1]), p

    # -- tensor-parallel construction ---------------------------------
    def shard(self, mp: int, devices=None, qkv_shard: str = "auto",
              compiled_step="auto",
              out_shard: str = "auto") -> "TokenServingModel":
        """Head-sharded tensor-parallel twin of this serving surface
        (inference/serving.py ShardedServingCore): the CORE's qkv
        projections split by head over ``mp`` mesh shards and each
        layer closes with one all-reduce, while the embedding table
        and readout head stay REPLICATED (shared by reference — they
        are row-independent and the engines sample from one replica).
        Every engine built on the sharded twin gets a matching
        sharded ``PagedKVCache`` automatically (``for_model`` reads
        ``mp``/``shard_devices`` off the core) — pool HBM per device
        drops by mp x, streams stay bit-identical to the single-chip
        engine. A ``truncated_draft`` of the sharded twin is built
        from the base float layers and stays UNSHARDED (the draft is
        small by construction; sharding it would spend collectives
        on proposals the target re-verifies anyway)."""
        from .serving import ShardedServingCore
        core = self.core.base if isinstance(self.core,
                                            ShardedServingCore) \
            else self.core
        return TokenServingModel(
            ShardedServingCore(core, mp, devices=devices,
                               qkv_shard=qkv_shard,
                               compiled_step=compiled_step,
                               out_shard=out_shard),
            self._embed_np, self.lm_head,
            weight_dtype=self.weight_dtype, final_norm=self.final_norm,
            norm_eps=self.norm_eps, input_scale=self.input_scale)

    # -- draft construction -------------------------------------------
    def truncated_draft(self, num_layers: int) -> "TokenServingModel":
        """A draft that runs only the first ``num_layers`` of the core
        (weights SHARED by array reference — jnp arrays are immutable)
        behind the same embedding/readout. The cheapest 'distilled'
        draft: useful when the deep layers refine rather than redirect
        the argmax."""
        from ..incubate.nn.fused_transformer import FusedMultiTransformer
        m = self.core
        if num_layers >= m.num_layers:
            raise ValueError("draft must be shallower than the target")
        if hasattr(m, "truncated"):
            # cores that know how to truncate themselves (MoE: routed
            # expert blocks, not dense ffn1/ffn2) hand back a
            # weight-sharing twin of their first layers
            return TokenServingModel(m.truncated(num_layers),
                                     self._embed_np, self.lm_head,
                                     weight_dtype=self.weight_dtype)
        d = FusedMultiTransformer(
            m.embed_dim, m.num_heads,
            m.layers[0].ffn1.weight.shape[1],
            activation=m._act_name, num_layers=num_layers,
            normalize_before=m.normalize_before,
            epsilon=m.layers[0].ln._epsilon)
        for dst, src in zip(d.layers, m.layers):
            for name in ("ln", "qkv", "out_proj", "ffn_ln", "ffn1",
                         "ffn2"):
                dmod, smod = getattr(dst, name), getattr(src, name)
                for pname, par in smod._parameters.items():
                    if par is not None and \
                            dmod._parameters.get(pname) is not None:
                        dmod._parameters[pname]._data = par.data
        return TokenServingModel(d, self._embed_np, self.lm_head,
                                 weight_dtype=self.weight_dtype)


class _SpecSeq:
    """Host-side token state of one request: the full stream (prompt +
    every emitted token; the LAST entry is the pending token — emitted
    to the caller but not yet consumed by the models). Branch-group
    members additionally carry their group id / branch index, their
    private snapshot-carried RNG lane (``branch_lane_seed``) and the
    name of their grammar mask. ``toks`` is the list it is GIVEN, kept
    (hand it one of its own): the engine's request of the same rid
    reads its keys from that list (``PagedRequest.keys``), one token id
    a history row, as far as its history goes."""

    __slots__ = ("rid", "toks", "prompt_len", "slot", "started",
                 "lane", "gid", "branch", "mask")

    def __init__(self, rid: int, prompt: List[int]):
        self.rid = rid
        self.toks: List[int] = prompt
        self.prompt_len = len(prompt)
        self.slot: Optional[int] = None
        self.started = False    # first token sampled at admission?
        self.lane: Optional[np.random.RandomState] = None
        self.gid: Optional[int] = None
        self.branch = 0
        self.mask: Optional[str] = None

    @property
    def n_generated(self) -> int:
        return len(self.toks) - self.prompt_len


class SpeculativeEngine:
    """Draft/verify/rollback speculative decoding behind a token-ID
    API. ``target``/``draft`` are TokenServingModels; ``draft=None``
    with ``k > 0`` self-drafts with the target model (useful as a
    correctness harness — acceptance is then ~100% in greedy mode but
    there is no speedup); ``k = 0`` disables speculation entirely and
    serves plain token-ID paged decode (the baseline).

    Protocol: ``submit(token_ids) -> rid``; ``step() -> {rid: [tokens
    emitted this round]}``; ``tokens(rid)`` the full stream;
    ``release(rid)`` frees the pages. Capacity-finished requests land
    in ``finished`` as (rid, total_tokens) — their PAGES are already
    freed, but the host-side token stream stays readable via
    ``tokens(rid)`` until the caller ``release(rid)``s it, so a
    long-running server must release finished rids too or the
    per-request stream state accumulates. Engine events (admission,
    preemption with re-prefill, prefix caching) ride the wrapped
    PagedServingEngine and are reconciled between rounds; accounting
    lives in ``stats`` (SpecDecodeStats) next to the engine's
    ``prefix_stats``."""

    def __init__(self, target: TokenServingModel,
                 draft: Optional[TokenServingModel] = None, *,
                 k: int = 4, max_batch: int, block_size: int,
                 num_blocks: int,
                 max_blocks_per_seq: Optional[int] = None,
                 draft_num_blocks: Optional[int] = None,
                 prefix_cache: bool = False, sampling: str = "greedy",
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 watermark_blocks: int = 0,
                 chunk_tokens: Optional[int] = None,
                 prefill_token_budget: Optional[int] = None,
                 kv_dtype: str = "float32", seed: int = 0,
                 injector=None,
                 max_preemptions: Optional[int] = None,
                 numeric_guard: Optional[bool] = None,
                 tenants: Optional[Dict[str, dict]] = None,
                 collector=None, monitor=None, ledger=None):
        if k < 0:
            raise ValueError("k must be >= 0")
        if k > 0 and any(getattr(m.core, "layer_state", None)
                         for m in (target, draft) if m is not None):
            raise ValueError(
                "k > 0 with a state store: a rejected draft would have "
                "advanced the state store's rows of its slot, and the "
                "state store keeps no earlier rows to roll back to; "
                "serve a model with state layers at k 0")
        self.target = target
        self.k = int(k)
        self.draft = (draft if draft is not None else target) \
            if self.k > 0 else None
        self.sampling = sampling
        self.temperature = float(temperature)
        self.top_k = top_k
        self._rng = np.random.RandomState(seed)
        self.injector = injector
        # kv_dtype="int8" quantizes the TARGET pool (the quota/HBM
        # domain — ~2x block density at equal bytes); the draft pool
        # stays float (it is small by construction and its proposals
        # are verified anyway). prefill_token_budget composes with the
        # verify step since the step_multi refusal was lifted: each
        # round first streams pending prompt chunks, packed with the
        # verify rows on the kernel path.
        self.engine = PagedServingEngine(
            target.core, max_batch, block_size, num_blocks,
            max_blocks_per_seq=max_blocks_per_seq,
            dtype=kv_dtype,
            watermark_blocks=watermark_blocks,
            prefix_cache=prefix_cache, chunk_tokens=chunk_tokens,
            prefill_token_budget=prefill_token_budget,
            injector=injector, max_preemptions=max_preemptions,
            numeric_guard=numeric_guard, tenants=tenants,
            collector=collector, monitor=monitor, ledger=ledger)
        self.max_batch = self.engine.max_batch
        self.stats = SpecDecodeStats()
        # the speculative layer's stats export through the SAME
        # unified registry as the engine's siblings
        self.engine.registry.attach("spec", self.stats)
        self.finished: List[Tuple[int, int]] = []
        # terminal RequestOutcomes forwarded from the wrapped engine
        # (FINISHED and every FAILED_*); the caller drains this list
        self.outcomes: List[RequestOutcome] = []
        self._seqs: Dict[int, _SpecSeq] = {}     # by target slot
        self._by_rid: Dict[int, _SpecSeq] = {}
        # draft slots whose cache could not be (re)built after a
        # draft-pool OOM: rounds run unspeculated until a rebuild
        # lands (the verify path never depends on draft state)
        self._draft_dirty: set = set()
        # branch groups (fork-shared parallel decoding): per-gid meta
        # — seed / mask / best-of policy plus the member rid list. The
        # SLOT-level group truth (reservations, live set, page audit)
        # lives in the wrapped engine's _GroupTable; this layer owns
        # the RNG lanes and the outcome policy.
        self._groups: Dict[int, dict] = {}
        if self.k > 0:
            # second, smaller pool: same per-seq page capacity as the
            # target (the draft never runs ahead of the target's
            # verified length within a round), fully reservable for
            # every slot so a mid-roll draft OOM cannot happen — the
            # TARGET pool stays the only preemption authority
            mbps = self.engine.cache.max_blocks_per_seq
            if draft_num_blocks is None:
                draft_num_blocks = self.max_batch * mbps + 1
            self.draft_cache = PagedKVCache.for_model(
                self.draft.core, block_size, draft_num_blocks,
                max_seqs=self.max_batch, max_blocks_per_seq=mbps)
            self._draft_lens = np.zeros(self.max_batch, np.int32)
            if injector is not None:
                self.draft_cache.allocator.fault_hook = \
                    lambda n: injector.on_alloc("draft", n)
            if ledger is not None:
                # the draft pool's rows are priced by the DRAFT
                # model's own (smaller) work model
                ledger.bind_draft(self.draft.core)
        else:
            self.draft_cache = None

    # -- submission / events ------------------------------------------
    def submit(self, token_ids, *,
               max_preemptions: Optional[int] = None,
               deadline_steps: Optional[int] = None,
               deadline_s: Optional[float] = None,
               tenant_id: Optional[str] = None,
               resume: bool = False, n: int = 1,
               seed: Optional[int] = None, best_of: bool = False,
               logit_mask: Optional[str] = None) -> int:
        """Queue a token-ID prompt; admission (now or later) samples
        the first token on-device and prefills the draft cache. The
        resilience and tenancy knobs pass straight through to the
        wrapped PagedServingEngine (see its ``submit``); terminal
        RequestOutcomes — including a health-based
        ``REJECTED_ADMISSION`` — surface in ``outcomes``.

        ``n > 1`` admits a BRANCH GROUP: the prompt prefills once,
        then the scheduler COW-forks n slots over the same prompt
        pages and each branch samples its first token from the SHARED
        prefill hidden. The returned rid is the lead's == the group
        id; branch rids appear in ``group(gid)["rids"]`` as they fork.
        ``seed`` gives every branch an independent snapshot-carried
        RNG lane, ``branch_lane_seed(seed, i)`` — the n streams are
        bit-identical to n independent submits with those seeds (seed
        on a lone request is lane 0 of a group of one). ``best_of``
        makes the group race: the first member to finish wins and the
        losers are cancelled (pages freed, ``bestof_pruned`` waste).
        ``logit_mask`` names a ``register_logit_mask`` grammar applied
        to every lane of the request.

        ``resume=True`` HANDS OFF a stream that was already running on
        another engine (the disaggregated router's resubmission path,
        inference/router.py): the LAST token of ``token_ids`` is an
        already-sampled, not-yet-consumed PENDING token — exactly the
        host-side state a preempted request carries — so admission
        prefills only ``token_ids[:-1]`` and does NOT sample a first
        token; the first round after admission consumes the pending
        token through the normal decode path. This is what makes a
        cross-engine handoff BIT-IDENTICAL to the uninterrupted run:
        a fresh submit of prompt+generated would re-sample the
        handoff token from a multi-row PREFILL hidden, while the
        donor engine sampled it from a one-row DECODE hidden — the
        two executables differ by accumulation order (the
        MIN_PREFILL_SUFFIX_ROWS trap), so only the resume path keeps
        the stream's bytes. Requires >= 2 tokens (a nonempty prompt
        plus the pending token)."""
        toks = [int(t) for t in np.asarray(token_ids).reshape(-1)]
        if not toks:
            raise ValueError("empty prompt")
        if resume and len(toks) < 2:
            raise ValueError(
                "resume=True needs >= 2 tokens: a nonempty consumed "
                "prefix plus the pending (sampled, unconsumed) token")
        if resume and n > 1:
            raise ValueError("a resumed (handed-off) stream is one "
                             "branch — submit it with n=1")
        if best_of and n <= 1:
            raise ValueError("best_of needs n > 1 branches to race")
        if logit_mask is not None:
            logit_mask_fn(logit_mask)   # fail unknown names loudly now
        prefix = toks[:-1] if resume else toks
        col = self.engine.collector
        if col is not None:
            col.span_begin("submit.embed", counters=True)
        try:
            rows = self.target.embed(prefix)
        finally:
            if col is not None:
                col.span_end(tokens=len(prefix))
        # the gathered rows are the request's from here on: frozen, so
        # that the engine can see nobody writes to them and keeps them
        # without a copy (scheduler._request_rows). Its block hashes
        # are made from ``toks``, the stream this wrapper appends to
        rows.flags.writeable = False
        rid = self.engine.submit(rows,
                                 max_preemptions=max_preemptions,
                                 deadline_steps=deadline_steps,
                                 deadline_s=deadline_s,
                                 tenant_id=tenant_id, n=n, keys=toks)
        seq = _SpecSeq(rid, toks)
        seq.mask = logit_mask
        if seed is not None:
            seq.lane = np.random.RandomState(branch_lane_seed(seed, 0))
        if n > 1:
            seq.gid = rid
            self._groups[rid] = {
                "gid": rid, "n": int(n), "seed": seed,
                "best_of": bool(best_of), "mask": logit_mask,
                "prompt": list(toks), "rids": [rid],
                "next_branch": 1, "done": False, "winner": None,
                "released": []}
        if resume:
            # prompt_len counts the whole handed-off stream: THIS
            # engine generated none of it, so generated(rid) reports
            # only tokens minted here (the router owns the global
            # stream record). started=True suppresses the admission
            # sample — the pending token is toks[-1].
            seq.prompt_len = len(toks)
            seq.started = True
        self._by_rid[rid] = seq
        self._handle_events()
        return rid

    def set_tenant(self, tenant_id: str, **cfg):
        """Register/reconfigure a tenant on the wrapped engine (the
        TARGET pool is the quota domain; the draft pool is fully
        reservable by construction and carries attribution only)."""
        return self.engine.set_tenant(tenant_id, **cfg)

    @property
    def tenant_stats(self):
        return self.engine.tenant_stats

    def tenant_report(self):
        return self.engine.tenant_report()

    def export_slice(self, rid: int) -> Optional[dict]:
        """Migration export of ``rid``'s finished prefix pages from
        the TARGET pool (the draft pool is derived state — a migrated
        request's new host rebuilds it from the token stream like any
        re-admission). See PagedServingEngine.export_request_slice."""
        return self.engine.export_request_slice(rid)

    def import_slice(self, slc: dict) -> int:
        """Adopt a migrated slice into the TARGET pool (cached-free +
        hash-indexed; the resubmitted request's admission adopts)."""
        return self.engine.import_slice(slc)

    def tokens(self, rid: int) -> List[int]:
        """Full stream (prompt + generated) of a request."""
        return list(self._by_rid[rid].toks)

    def generated(self, rid: int) -> List[int]:
        seq = self._by_rid[rid]
        return list(seq.toks[seq.prompt_len:])

    def release(self, rid: int) -> None:
        """Caller-side finish: free the request's pages (both pools)
        and refill from the queue. A request released before it was
        ever admitted leaves the engine queue too — otherwise a later
        refill would admit an orphan slot this wrapper no longer
        tracks."""
        seq = self._by_rid.pop(rid)
        g = self._groups.get(seq.gid) if seq.gid is not None else None
        if g is not None:
            g["released"].append(rid)
            if set(g["rids"]) <= set(g["released"]):
                # last member released: the group record drains
                del self._groups[seq.gid]
        if seq.slot is not None:
            slot = seq.slot
            self._seqs.pop(slot, None)
            seq.slot = None
            self._clear_draft_slot(slot)
            self.engine.release(slot)   # frees pages + refills
        else:
            for req in list(self.engine.queue):
                if req.rid == rid:
                    self.engine._dequeue(req)
        self._handle_events()

    def group(self, gid: int) -> Optional[dict]:
        """Live branch-group record (None once every member has been
        released): member ``rids`` in branch order, the best-of
        ``winner``, seed / mask — the outcome-delivery unit for
        parallel sampling."""
        g = self._groups.get(gid)
        return None if g is None else dict(g)

    def cancel(self, rid: int) -> bool:
        """Deliberate early stop of one stream (beam cuts, caller
        cancel; best-of loser pruning calls the same path): the
        wrapped engine frees the pages and records a CANCELLED
        outcome, this layer detaches the stream from its slot — the
        partial tokens stay readable via ``tokens(rid)`` until
        ``release(rid)``."""
        ok = self.engine.cancel(rid)
        self._handle_events()
        return ok

    def fork_stream(self, rid: int) -> int:
        """Beam/tree primitive at the token level: clone a RUNNING
        stream into a free slot (engine ``fork_stream`` — pages
        COW-shared at the current length, fresh rid, the source's
        group grows by the clone). The clone copies the host-side
        stream including the pending token, inherits the grammar
        mask, gets its own RNG lane (``branch_lane_seed(seed,
        branch)`` when the source group is seeded; an unseeded-group
        clone duplicates the source's lane state; laneless sources
        clone laneless) and rebuilds its draft cache from the stream.
        Returns the clone's rid."""
        seq = self._by_rid[rid]
        if seq.slot is None:
            raise ValueError(f"rid {rid} is not an active stream")
        brid = self.engine.fork_stream(rid)
        bslot, breq = None, None
        for s2, r in enumerate(self.engine._requests):
            if r is not None and r.rid == brid:
                bslot, breq = s2, r
                break
        assert breq is not None, "engine fork_stream lost its clone"
        gid = breq.gid
        seq.gid = gid
        g = self._groups.get(gid)
        if g is None:
            # on-demand group for a previously lone stream (mirrors
            # the engine's _GroupTable create): seedless unless the
            # source was — a lone seeded submit records no group, so
            # its clones duplicate the lane state instead
            g = {"gid": gid, "n": 1, "seed": None, "best_of": False,
                 "mask": seq.mask,
                 "prompt": list(seq.toks[:seq.prompt_len]),
                 "rids": [rid], "next_branch": 1, "done": False,
                 "winner": None, "released": []}
            self._groups[gid] = g
        g["n"] += 1
        branch = breq.branch
        g["next_branch"] = max(g["next_branch"], branch + 1)
        g["rids"].append(brid)
        clone = _SpecSeq(brid, list(seq.toks))
        clone.prompt_len = seq.prompt_len
        self._bind_keys(breq, clone)
        clone.started = True
        clone.slot = bslot
        clone.gid = gid
        clone.branch = branch
        clone.mask = seq.mask
        if g["seed"] is not None:
            clone.lane = np.random.RandomState(
                branch_lane_seed(g["seed"], branch))
        elif seq.lane is not None:
            clone.lane = np.random.RandomState(0)
            clone.lane.set_state(seq.lane.get_state())
        self._by_rid[brid] = clone
        self._seqs[bslot] = clone
        try:
            self._draft_prefill(bslot, clone)
            self._draft_dirty.discard(bslot)
        except BlockOOM:
            self._clear_draft_slot(bslot)
            self._draft_dirty.add(bslot)
        return brid

    @staticmethod
    def _bind_keys(req, seq: _SpecSeq) -> None:
        """An engine request that was cut from another (a fork, a
        branch) or loaded from a snapshot holds the keys of the rows it
        had then: from here on it reads them from ``seq``'s stream,
        which this wrapper goes on appending to. A request without
        keys (a snapshot from before requests had them) keeps hashing
        its rows."""
        if req is not None and req.keys is not None:
            req.bind_keys(seq.toks)

    def _clear_draft_slot(self, slot: int) -> None:
        if self.draft_cache is not None:
            self.draft_cache.free_seq(slot)
            self._draft_lens[slot] = 0
        self._draft_dirty.discard(slot)

    def _sample(self, model: TokenServingModel, logits,
                rng_rows: Optional[list] = None, logit_mask=None):
        return model.sample(logits, mode=self.sampling,
                            temperature=self.temperature,
                            top_k=self.top_k, rng=self._rng,
                            rng_rows=rng_rows, logit_mask=logit_mask,
                            collector=self.engine.collector)

    def _lane_rows(self, slots, L: int) -> Optional[list]:
        """Per-flat-row RNG lanes for a [max_batch, L]-row sample: row
        s*L+l draws from slot s's lane; laneless slots (and inactive
        trash rows) keep the shared engine RNG. None when no active
        stream carries a lane — the batched draw path then stays
        bit-identical to the pre-group engine."""
        if not any(self._seqs[s].lane is not None for s in slots):
            return None
        rows: List[Optional[np.random.RandomState]] = \
            [None] * (self.max_batch * L)
        for s in slots:
            lane = self._seqs[s].lane
            if lane is not None:
                for pos in range(L):
                    rows[s * L + pos] = lane
        return rows

    def _mask_next(self, model: TokenServingModel, slots,
                   extra: Dict[int, List[int]]):
        """bool[max_batch, vocab] grammar mask for sampling ONE next
        token per slot (the draft roll): row s masks the token
        following stream(s) + extra[s] (the proposals rolled so far).
        None when no active stream carries a mask."""
        masked = [s for s in slots if self._seqs[s].mask is not None]
        if not masked:
            return None
        V = model.vocab_size
        m = np.ones((self.max_batch, V), bool)
        for s in masked:
            seq = self._seqs[s]
            fn = logit_mask_fn(seq.mask)
            m[s] = np.asarray(
                fn(list(seq.toks) + list(extra.get(s, [])), V), bool)
        return m

    def _mask_rows(self, model: TokenServingModel, slots,
                   drafts: Dict[int, List[int]], L: int):
        """bool[max_batch, L, vocab] grammar mask for the multi-token
        verify sample: row (s, l) masks the token following
        stream(s) + drafts[s][:l] — the context each verify position
        scores. None when no active stream carries a mask."""
        masked = [s for s in slots if self._seqs[s].mask is not None]
        if not masked:
            return None
        V = model.vocab_size
        m = np.ones((self.max_batch, L, V), bool)
        for s in masked:
            seq = self._seqs[s]
            fn = logit_mask_fn(seq.mask)
            for pos in range(L):
                m[s, pos] = np.asarray(
                    fn(list(seq.toks) + drafts[s][:pos], V), bool)
        return m

    def _handle_events(self) -> None:
        """Reconcile wrapped-engine events: preemptions drop the draft
        slot (the token stream and pending token survive host-side);
        admissions sample the first token (fresh requests only — a
        re-admitted request keeps its pending token, so the emitted
        stream never forks) and prefill the draft cache from the
        stream."""
        eng = self.engine
        for rid in eng.preempted:
            seq = self._by_rid.get(rid)
            if seq is None or seq.slot is None:
                continue
            self._seqs.pop(seq.slot, None)
            self._clear_draft_slot(seq.slot)
            seq.slot = None
        eng.preempted.clear()
        for oc in eng.outcomes:
            # failure outcomes (shed / numeric / deadline): detach the
            # stream from its slot — the host-side tokens stay
            # readable via tokens(rid) until the caller releases
            if oc.failed:
                seq = self._by_rid.get(oc.rid)
                if seq is not None and seq.slot is not None:
                    self._seqs.pop(seq.slot, None)
                    self._clear_draft_slot(seq.slot)
                    seq.slot = None
            self.outcomes.append(oc)
        eng.outcomes.clear()
        for rid, slot, length in eng.finished:
            # engine-side capacity release (only reachable through
            # engine.step, which this wrapper does not call — but keep
            # the books straight if a caller mixes the APIs)
            seq = self._by_rid.get(rid)
            if seq is not None:
                self._seqs.pop(slot, None)
                self._clear_draft_slot(slot)
                seq.slot = None
                self.finished.append((rid, len(seq.toks)))
                self._member_done(seq)
        eng.finished.clear()
        for rid, slot, h in eng.admitted:
            seq = self._by_rid.get(rid)
            if seq is None:
                seq = self._adopt_branch(rid)
                if seq is not None:
                    self._bind_keys(eng._requests[slot], seq)
            if seq is None:
                # released while queued (release() drops queued
                # requests, so this is a belt-and-braces path): never
                # leave an engine slot active that this wrapper does
                # not track
                eng.release(slot)
                continue
            seq.slot = slot
            self._seqs[slot] = seq
            if not seq.started:
                m = None
                if seq.mask is not None:
                    m = np.asarray(logit_mask_fn(seq.mask)(
                        list(seq.toks), self.target.vocab_size),
                        bool)[None]
                rows = None if seq.lane is None else [seq.lane]
                tok, _ = self._sample(self.target, self.logits_of(h),
                                      rng_rows=rows, logit_mask=m)
                seq.toks.append(int(tok.reshape(-1)[0]))
                seq.started = True
            try:
                self._draft_prefill(slot, seq)
                self._draft_dirty.discard(slot)
            except BlockOOM:
                # injected draft-pool OOM: serve the slot without a
                # draft until a rebuild lands — never fail the request
                # over its DRAFT state
                self._clear_draft_slot(slot)
                self._draft_dirty.add(slot)
        eng.admitted.clear()

    def _adopt_branch(self, rid: int) -> Optional[_SpecSeq]:
        """First sight of a branch rid the scheduler fork minted (an
        admitted event with no _SpecSeq yet): build the branch's
        stream state — prompt copy, deterministic branch index, RNG
        lane ``branch_lane_seed(seed, branch)``, the group's mask —
        so the caller's admission loop samples its first token from
        the SHARED prefill hidden like any admission. Returns None
        for rids that belong to no live group (the orphan-release
        path keeps those). Branch indices follow admitted-event order,
        which is the scheduler's fork order — deterministic, so a
        replayed run adopts identical lanes."""
        gid = self.engine.groups.gid_of(rid)
        g = self._groups.get(gid) if gid is not None else None
        if g is None:
            return None
        branch = g["next_branch"]
        g["next_branch"] = branch + 1
        g["rids"].append(rid)
        seq = _SpecSeq(rid, list(g["prompt"]))
        seq.gid = g["gid"]
        seq.branch = branch
        seq.mask = g["mask"]
        if g["seed"] is not None:
            seq.lane = np.random.RandomState(
                branch_lane_seed(g["seed"], branch))
        self._by_rid[rid] = seq
        return seq

    def _member_done(self, seq: _SpecSeq) -> None:
        """Group outcome policy on a member finishing: under
        ``best_of`` the FIRST member to finish wins and every other
        live member is cancelled — pages freed through the normal
        drop path, CANCELLED outcome, pending ledger rows resolved as
        ``bestof_pruned`` waste. Without best_of, members finish
        independently and the record drains at release. The
        cancellations' outcomes land in the engine event queues and
        are drained by the next ``_handle_events`` pass (every round
        starts with one)."""
        g = self._groups.get(seq.gid) if seq.gid is not None else None
        if g is None or not g["best_of"] or g["done"]:
            return
        g["done"] = True
        g["winner"] = seq.rid
        for rid in list(g["rids"]):
            if rid != seq.rid and rid in self._by_rid:
                self.engine.cancel(rid)

    def logits_of(self, hidden) -> Tensor:
        return self.target.logits(hidden)

    @property
    def resilience_stats(self):
        return self.engine.resilience_stats

    @property
    def collector(self):
        """The wrapped engine's TraceCollector (None when tracing is
        off) — the speculative layer records its round spans there."""
        return self.engine.collector

    @property
    def ledger(self):
        """The wrapped engine's CostLedger (None when accounting is
        off) — the speculative layer reports its draft-pool work
        there."""
        return self.engine.ledger

    @property
    def registry(self):
        """The unified MetricsRegistry (wrapped engine's, with this
        layer's SpecDecodeStats attached under ``spec``)."""
        return self.engine.registry

    @property
    def monitor(self):
        """The wrapped engine's HealthMonitor (None when monitoring
        is off) — it samples the unified registry, ``spec.*``
        included, at the end of every engine step."""
        return self.engine.monitor

    def check_invariants(self) -> bool:
        """Audit the wrapped engine + BOTH pools (target and draft).
        Draft-side extras: slot alignment (every tracked stream's
        draft table covers its draft length; untracked slots hold no
        draft pages) — see PagedKVCache.check_invariants for the
        pool-level list."""
        self.engine.check_invariants()
        if self.draft_cache is not None:
            tracked = np.zeros(self.max_batch, bool)
            for s in self._seqs:
                tracked[s] = True
            self.draft_cache.check_invariants(lens=self._draft_lens,
                                              active=tracked)
            for s in range(self.max_batch):
                if not tracked[s]:
                    assert not self.draft_cache.seq_blocks[s], \
                        (f"draft slot {s} holds pages with no tracked "
                         f"stream")
        return True

    def _draft_prefill(self, slot: int, seq: _SpecSeq) -> None:
        """(Re-)build the draft cache for a slot from the token stream
        (everything but the pending token — exactly what the target
        has consumed), through the SAME chunked-prefill path the
        target engine uses: K/V stream straight into the draft pool's
        pages, no dense scratch, no scatter pass."""
        if self.draft_cache is None:
            return
        consumed = seq.toks[:-1]
        cap = self.draft_cache.capacity_per_seq
        if len(consumed) > cap:
            raise ValueError("draft capacity exceeded")   # unreachable
        self._clear_draft_slot(slot)
        # mirror the target slot's tenant onto the draft slot: the
        # draft pool is not a quota domain (it is fully reservable by
        # construction), but its OOM messages and charge audit then
        # attribute draft pages to the right tenant too
        req = self.engine._requests[slot]
        if req is not None:
            self.draft_cache.set_seq_tenant(slot, req.tenant)
        chunked_prefill(self.draft.core, self.draft_cache, slot,
                        self.draft.embed(consumed),
                        chunk_tokens=self.engine.chunk_tokens)
        self._draft_lens[slot] = len(consumed)
        led = self.engine.ledger
        if led is not None:
            # a first build is fresh draft work; a rebuild (preempt /
            # dirty-slot recovery) recomputes rows below the draft
            # high-water mark — the ledger splits replay vs fresh
            led.on_draft_prefill(seq.rid, 0, len(consumed))

    # -- the speculative round ----------------------------------------
    def step(self) -> Dict[int, List[int]]:
        """One draft/verify/rollback round over every active slot.
        Returns {rid: tokens emitted this round} (>= 1 token per
        active request). Capacity-finished requests are released and
        reported in ``finished`` instead.

        With a collector installed the round records a ``spec_round``
        span wrapping ``draft_roll`` (the k-token roll), the verify
        step span (``step_multi``'s own bracket) and
        ``sample_verify`` (target sampling + accept/rollback + draft
        rebuilds); the span stack unwinds cleanly even when an
        injected ``EngineCrash`` tears the round down mid-flight."""
        col = self.engine.collector
        depth = col.span_depth if col is not None else 0
        if col is not None:
            col.span_begin("spec_round")
        try:
            out = self._step_impl(col)
        except BaseException:
            # an EngineCrash mid-round: close the open spans flagged
            # aborted so the trace shows where the round died
            if col is not None:
                col.span_unwind(depth, aborted=True)
            raise
        if col is not None:
            col.span_unwind(depth)      # closes spec_round normally
        return out

    def _step_impl(self, col) -> Dict[int, List[int]]:
        import paddle_tpu as paddle
        eng = self.engine
        led = eng.ledger
        if self.injector is not None:
            # draft-phase faults share the verify step's clock: label
            # the round with the upcoming step_multi index
            self.injector.begin_step(eng._step_count + 1)
        # requests at page capacity cannot take another token: retire.
        # Loop to a fixed point — a release can refill the slot with a
        # queued prompt that is ITSELF at capacity (a full-length
        # prompt generates nothing), which must retire too rather
        # than crash the multi-token capacity check below.
        while True:
            self._handle_events()
            full = [s for s in sorted(self._seqs)
                    if int(eng.lens[s]) >= eng.max_len]
            if not full:
                break
            for slot in full:
                seq = self._seqs.pop(slot)
                self.finished.append((seq.rid, len(seq.toks)))
                seq.slot = None
                self._clear_draft_slot(slot)
                eng.release(slot)
                # best-of: first finisher wins, losers cancel (their
                # outcomes drain on the next _handle_events pass —
                # the loop top runs one before anything samples)
                self._member_done(seq)
        slots = sorted(self._seqs)
        if not slots and eng.prefill_token_budget is not None and \
                (eng.num_prefilling > 0 or eng._queue_len):
            # token-budget mode with every tracked stream still
            # mid-prefill: run an (empty-verify) engine step so the
            # pending prompts keep streaming — admitted events land in
            # _handle_events and next round verifies their pending
            # token
            eng.step_multi(paddle.to_tensor(
                np.zeros((self.max_batch, 1, self.target.d_model),
                         np.float32)))
            self._handle_events()
            return {}
        if not slots:
            # a fault storm can empty the whole batch mid-round
            # (everything preempted/shed): kick admission so queued
            # and preempted requests re-enter, then serve next round.
            # The kick consumes an engine step of its own — exactly
            # like an admission-only PagedServingEngine.step — so
            # step-keyed fault schedules expire even when admission
            # itself is the faulted path (no injection deadlock)
            if eng._queue_len:
                eng._begin_step(kind="admission_kick")
                ok = False
                try:
                    eng._try_admit()
                    ok = True
                finally:
                    # the kick consumes an engine step of its own —
                    # close its telemetry span like any other step
                    # (aborted when an injected crash tears the kick,
                    # so the monitor never samples torn state)
                    eng._end_step_telemetry(aborted=not ok)
                self._handle_events()
            return {}
        B = self.max_batch
        # every active slot rides every call, so the speculation depth
        # clamps to the tightest remaining capacity
        remaining = min(eng.max_len - int(eng.lens[s]) for s in slots)
        L = max(1, min(self.k + 1, remaining))
        k_eff = L - 1

        # 1. draft roll: k_eff proposals, then one append-only step so
        #    the draft cache ends the round at the target's length
        #    (uniform rollback, no per-slot catch-up next round).
        #    A draft-pool BlockOOM mid-roll (injected, or a caller-
        #    sized-down draft pool) rolls the PARTIAL roll back
        #    page-wise and serves the round without speculation — the
        #    target pool is never touched by a draft fault, and the
        #    draft slots rebuild from the token stream after the
        #    verify (the same known-good path a preemption takes).
        if col is not None:
            col.span_begin("draft_roll")
        if self._draft_dirty:
            # some slot is missing its draft cache: no proposals this
            # round, but CLEAN slots still lockstep below — only the
            # dirty ones rebuild (never the whole batch, every round)
            k_eff = 0
            L = 1
        pre_draft = {s: int(self._draft_lens[s]) for s in slots} \
            if self.draft_cache is not None else {}
        roll_oom = False      # fresh draft-pool OOM THIS round
        drafts: Dict[int, List[int]] = {s: [] for s in slots}
        dprobs: Dict[int, List[np.ndarray]] = {s: [] for s in slots}
        if self.draft_cache is not None and k_eff > 0:
            cur = {s: self._seqs[s].toks[-1] for s in slots}
            d_d = self.draft.d_model
            try:
                for j in range(k_eff + 1):
                    x = np.zeros((B, 1, d_d), np.float32)
                    for s in slots:
                        x[s, 0] = self.draft.embed(cur[s])
                        self.draft_cache.ensure(
                            s, int(self._draft_lens[s]) + 1)
                    # a copy: _draft_lens advances in place below
                    t = Tensor(np.array(self._draft_lens, np.int32))
                    with no_grad():
                        out, _ = self.draft.core(
                            paddle.to_tensor(x),
                            caches=self.draft_cache.views, time_step=t)
                    for s in slots:
                        self._draft_lens[s] += 1
                    self.stats.draft_steps += len(slots)
                    if led is not None:
                        led.on_draft_rows(
                            [(self._seqs[s].rid,
                              int(self._draft_lens[s]) - 1)
                             for s in slots])
                    if j < k_eff:
                        lg = self.draft.logits(out[:, -1])
                        if self.injector is not None:
                            lg = self.injector.corrupt_draft_logits(lg)
                        toks, probs = self._sample(
                            self.draft, lg,
                            rng_rows=self._lane_rows(slots, 1),
                            logit_mask=self._mask_next(
                                self.draft, slots, drafts))
                        for s in slots:
                            drafts[s].append(int(toks[s]))
                            if probs is not None:
                                dprobs[s].append(probs[s])
                            cur[s] = int(toks[s])
            except BlockOOM:
                # page-level rollback of the partial roll: appended
                # draft pages fall off the table tails, target state
                # untouched; this round verifies the pending token only
                for s in slots:
                    if led is not None and \
                            int(self._draft_lens[s]) > pre_draft[s]:
                        led.on_draft_truncate(
                            self._seqs[s].rid, pre_draft[s],
                            int(self._draft_lens[s]),
                            cause="draft_oom")
                    self.draft_cache.truncate(s, pre_draft[s])
                    self._draft_lens[s] = pre_draft[s]
                drafts = {s: [] for s in slots}
                dprobs = {s: [] for s in slots}
                k_eff, L = 0, 1
                roll_oom = True
                self.stats.draft_oom_rolls += 1
        elif self.draft_cache is not None:
            # depth clamped to 0 (capacity, or a dirty slot): keep the
            # CLEAN slots' draft caches in lockstep by consuming the
            # pending token alongside the target; dirty slots ride as
            # trash rows and rebuild after the verify
            live = [s for s in slots if s not in self._draft_dirty]
            if live:
                try:
                    x = np.zeros((B, 1, self.draft.d_model), np.float32)
                    for s in live:
                        x[s, 0] = self.draft.embed(
                            self._seqs[s].toks[-1])
                        self.draft_cache.ensure(
                            s, int(self._draft_lens[s]) + 1)
                    # a copy: _draft_lens advances in place below
                    t = Tensor(np.array(self._draft_lens, np.int32))
                    with no_grad():
                        self.draft.core(paddle.to_tensor(x),
                                        caches=self.draft_cache.views,
                                        time_step=t)
                    for s in live:
                        self._draft_lens[s] += 1
                    self.stats.draft_steps += len(live)
                    if led is not None:
                        led.on_draft_rows(
                            [(self._seqs[s].rid,
                              int(self._draft_lens[s]) - 1)
                             for s in live])
                except BlockOOM:
                    for s in live:
                        if led is not None and \
                                int(self._draft_lens[s]) > pre_draft[s]:
                            led.on_draft_truncate(
                                self._seqs[s].rid, pre_draft[s],
                                int(self._draft_lens[s]),
                                cause="draft_oom")
                        self.draft_cache.truncate(s, pre_draft[s])
                        self._draft_lens[s] = pre_draft[s]
                    roll_oom = True
                    self.stats.draft_oom_rolls += 1

        if col is not None:
            col.span_end(k=k_eff, oom_rolled=roll_oom)
        # 2. verify: ONE target call scores the pending token plus all
        #    k_eff proposals through the paged cache. The
        #    mid_spec_round crash point sits between the draft roll
        #    and the verify — the nastiest place to die: the draft
        #    pool has advanced but the target has verified nothing
        #    (recovery rebuilds the draft from the token streams, so
        #    nothing of the half-round survives into the restored
        #    engine).
        if self.injector is not None:
            self.injector.crash_point("mid_spec_round")
        d_t = self.target.d_model
        pre_lens = {s: int(eng.lens[s]) for s in slots}
        # the [B, L, d_model] input: gathered on the host, handed to
        # the device
        if col is not None:
            col.span_begin("embed")
        x = np.zeros((B, L, d_t), np.float32)
        for s in slots:
            x[s] = self.target.embed([self._seqs[s].toks[-1]]
                                     + drafts[s])
        x = paddle.to_tensor(x)
        if col is not None:
            col.span_end()
        out = eng.step_multi(x)
        if out is None:
            # every slot fell out mid-step (deadline/shed storm): the
            # outcomes carry the verdicts; nothing was scored
            self._handle_events()
            return {}
        if col is not None:
            col.span_begin("sample_verify")
        g_toks, g_probs = self._sample(
            self.target, self.target.logits(out),
            rng_rows=self._lane_rows(slots, L),
            logit_mask=self._mask_rows(self.target, slots, drafts, L))
        preempted_mid = {rid for rid in eng.preempted}
        failed_mid = {oc.rid for oc in eng.outcomes if oc.failed}

        # 3. accept + rollback per slot
        emitted_by_rid: Dict[int, List[int]] = {}
        for s in slots:
            seq = self._seqs.get(s)
            if seq is None or seq.rid in preempted_mid or \
                    seq.rid in failed_mid or not eng.active[s]:
                continue        # evicted/failed during verification
            d = drafts[s]
            if self.sampling == "greedy":
                n = 0
                while n < k_eff and d[n] == int(g_toks[s, n]):
                    n += 1
                emitted = d[:n] + [int(g_toks[s, n])]
            else:
                n, correction = self._reject_sample(
                    d, dprobs[s], g_probs[s], rng=seq.lane)
                bonus = int(g_toks[s, k_eff]) if n == k_eff \
                    else correction
                emitted = d[:n] + [bonus]
            new_len = pre_lens[s] + 1 + n
            eng.rollback(s, new_len)
            if self.draft_cache is not None and not roll_oom \
                    and s not in self._draft_dirty:
                # this slot's draft advanced in lockstep: align it to
                # the accepted length (dirty / OOM-rolled-back slots
                # are behind and rebuild below instead)
                if led is not None and \
                        int(self._draft_lens[s]) > new_len:
                    led.on_draft_truncate(
                        seq.rid, new_len, int(self._draft_lens[s]),
                        cause="spec_rejected")
                self.draft_cache.truncate(s, new_len)
                self._draft_lens[s] = new_len
            seq.toks.extend(emitted)
            self.stats.proposed += k_eff
            self.stats.accepted += n
            self.stats.rolled_back += k_eff - n
            self.stats.emitted += len(emitted)
            self.stats.target_steps += 1
            emitted_by_rid[seq.rid] = emitted
        if self.draft_cache is not None and \
                (roll_oom or self._draft_dirty):
            # rebuild draft caches from the token streams (the path a
            # preemption takes — deterministic replay): after a fresh
            # mid-roll OOM every slot's roll was rolled back, so all
            # rebuild once; otherwise only the DIRTY slots do (clean
            # ones stayed in lockstep above). A slot that OOMs again
            # stays dirty and serves unspeculated until the pool
            # clears.
            targets = list(self._seqs) if roll_oom \
                else list(self._draft_dirty)
            for s in targets:
                if s not in self._seqs or not eng.active[s]:
                    continue
                try:
                    self._draft_prefill(s, self._seqs[s])
                    self._draft_dirty.discard(s)
                except BlockOOM:
                    self._clear_draft_slot(s)
                    self._draft_dirty.add(s)
        if col is not None:
            col.span_end()
        self._handle_events()
        return emitted_by_rid

    def _reject_sample(self, d: List[int], q_rows: List[np.ndarray],
                       p_rows: np.ndarray,
                       rng: Optional[np.random.RandomState] = None
                       ) -> Tuple[int, int]:
        """Standard speculative rejection sampling: accept proposal
        d[i] with prob min(1, p_i[d_i] / q_i[d_i]); at the first
        rejection draw the correction from the residual
        normalize(max(p_i - q_i, 0)). Returns (n_accepted,
        correction_token) — correction is only meaningful when
        n_accepted < len(d). ``rng`` is the sequence's private RNG
        lane (branch groups); None keeps the shared engine RNG —
        laned streams consume accept/residual draws from their own
        lane only, the independence the bit-identity oracle needs."""
        r = self._rng if rng is None else rng
        for i, tok in enumerate(d):
            p_i = p_rows[i].astype(np.float64)
            q_i = q_rows[i].astype(np.float64)
            ratio = p_i[tok] / max(q_i[tok], 1e-30)
            if r.random_sample() < min(1.0, ratio):
                continue
            resid = np.maximum(p_i - q_i, 0.0)
            tot = resid.sum()
            if tot <= 0.0:      # p == q: accept-equivalent, take p draw
                resid, tot = p_i, p_i.sum()
            cdf = np.cumsum(resid / tot)
            c = int(np.searchsorted(cdf, r.random_sample(),
                                    side="right"))
            return i, min(c, len(p_i) - 1)
        return len(d), -1

    # -- checkpoint / restore -----------------------------------------
    def snapshot(self) -> dict:
        """Checkpoint the speculative layer: the wrapped engine's full
        snapshot (which includes the TARGET pool), every host-side
        token stream (_SpecSeq: prompt + emitted + pending token), the
        sampler RNG state (stochastic modes must draw the same
        sequence after a restore), stats, undrained events, and the
        dirty-slot set. The DRAFT pool is deliberately NOT serialized:
        it is a pure function of the token streams and restore
        rebuilds it through the same chunked-prefill path a
        preemption uses — half a snapshot's bytes for free."""
        return {
            "kind": "speculative_engine",
            "config": {"k": self.k, "sampling": self.sampling,
                       "temperature": self.temperature,
                       "top_k": self.top_k,
                       "draft_num_blocks":
                           (None if self.draft_cache is None
                            else self.draft_cache.num_blocks),
                       "self_draft": self.draft is self.target},
            "engine": self.engine.snapshot(),
            "seqs": [{"rid": s.rid, "toks": list(s.toks),
                      "prompt_len": s.prompt_len, "slot": s.slot,
                      "started": s.started, "gid": s.gid,
                      "branch": s.branch, "mask": s.mask,
                      "lane": (None if s.lane is None
                               else s.lane.get_state())}
                     for s in self._by_rid.values()],
            "rng": self._rng.get_state(),
            "stats": PagedServingEngine._stats_rec(self.stats),
            "finished": list(self.finished),
            "outcomes": [oc.as_dict() for oc in self.outcomes],
            "draft_dirty": sorted(self._draft_dirty),
            # branch groups: meta records (seed/mask/policy/members);
            # the per-branch LANE STATES ride in the seq records above
            # so a restored run draws the same streams
            "groups": [dict(g) for g in self._groups.values()],
        }

    @classmethod
    def restore(cls, target: TokenServingModel,
                draft: Optional[TokenServingModel], snap: dict, *,
                injector=None, collector=None,
                monitor=None, ledger=None) -> "SpeculativeEngine":
        """Rebuild a speculative engine from ``snapshot`` around the
        caller's models. The target engine restores exactly
        (PagedServingEngine.restore); the draft pool is REBUILT from
        the token streams slot by slot — chunked prefill of each
        stream minus its pending token, the same deterministic-replay
        path a preemption takes, so the rebuilt pages are bit-exact
        with the crashed pool's. A slot whose rebuild OOMs goes
        dirty and serves unspeculated until the pool clears (PR 5's
        machinery); fault hooks stay unwired during the rebuild so a
        stale injector schedule cannot fire outside a serving step."""
        cfg = snap["config"]
        ecfg = snap["engine"]["config"]
        if cfg["k"] > 0 and cfg.get("self_draft") is not None \
                and cfg["self_draft"] != (draft is None):
            # a wrong draft would not fail loudly: greedy streams stay
            # identical (silently different perf), sampling modes die
            # mid-replay with an opaque RecoveryError — name the
            # mismatch here instead
            raise ValueError(
                "draft-model mismatch: snapshot was taken with a "
                + ("self-drafted (draft=None)"
                   if cfg["self_draft"] else "separate draft")
                + " engine but restore() was given "
                + ("draft=None" if draft is None
                   else "a separate draft model"))
        # num_blocks=2: the constructor's TARGET engine (and its pool)
        # is replaced by the restored one just below — a placeholder
        # pool keeps recovery's peak at ONE target pool, not three
        # (constructor's + restore's + the one being discarded). The
        # DRAFT pool built here is real and kept.
        spec = cls(target, draft, k=cfg["k"],
                   max_batch=ecfg["max_batch"],
                   block_size=ecfg["block_size"],
                   num_blocks=2,
                   max_blocks_per_seq=ecfg["max_blocks_per_seq"],
                   draft_num_blocks=cfg["draft_num_blocks"],
                   prefix_cache=ecfg["prefix_cache"],
                   sampling=cfg["sampling"],
                   temperature=cfg["temperature"], top_k=cfg["top_k"],
                   watermark_blocks=ecfg["watermark_blocks"],
                   chunk_tokens=ecfg["chunk_tokens"],
                   injector=injector, collector=collector,
                   max_preemptions=ecfg["max_preemptions"],
                   numeric_guard=ecfg["numeric_guard"],
                   ledger=ledger)
        spec.engine = PagedServingEngine.restore(
            target.core, snap["engine"], injector=injector,
            collector=collector, monitor=monitor, ledger=ledger)
        spec.engine.registry.attach("spec", spec.stats)
        for rec in snap["seqs"]:
            seq = _SpecSeq(rec["rid"], list(rec["toks"]))
            seq.prompt_len = rec["prompt_len"]
            seq.slot = rec["slot"]
            seq.started = rec["started"]
            seq.gid = rec.get("gid")
            seq.branch = rec.get("branch", 0)
            seq.mask = rec.get("mask")
            lane = rec.get("lane")
            if lane is not None:
                seq.lane = np.random.RandomState(0)
                seq.lane.set_state(lane)
            spec._by_rid[seq.rid] = seq
            if seq.slot is not None:
                spec._seqs[seq.slot] = seq
            cls._bind_keys(spec.engine.request_of(seq.rid), seq)
        spec._rng.set_state(snap["rng"])
        spec._groups = {int(g["gid"]): dict(g)
                        for g in snap.get("groups", [])}
        PagedServingEngine._stats_set(spec.stats, snap["stats"])
        spec.finished = list(snap["finished"])
        spec.outcomes = [RequestOutcome(**oc)
                         for oc in snap["outcomes"]]
        # slots dirty at snapshot time STAY dirty (they held no draft
        # pages then, and a restored run must schedule identically to
        # the uninterrupted one — rebuilding them here would let a
        # replayed round speculate where the live round did not)
        dirty = {int(s) for s in snap["draft_dirty"]}
        if spec.draft_cache is not None:
            hook = spec.draft_cache.allocator.fault_hook
            spec.draft_cache.allocator.fault_hook = None
            try:
                for slot, seq in spec._seqs.items():
                    if slot in dirty:
                        continue
                    try:
                        spec._draft_prefill(slot, seq)
                    except BlockOOM:
                        spec._clear_draft_slot(slot)
                        spec._draft_dirty.add(slot)
            finally:
                spec.draft_cache.allocator.fault_hook = hook
        spec._draft_dirty.update(s for s in dirty if s in spec._seqs)
        spec.check_invariants()
        if monitor is not None:
            # re-baseline AFTER the spec stats re-attached above: the
            # engine-level rebase ran before ``spec.*`` existed in the
            # registry, so a fresh monitor's first delta would see the
            # restored spec counters as a step-one jump. Refreshing at
            # the same step folds them into the baseline (a no-op for
            # a monitor that lived through the crash).
            monitor.rebase(spec.engine._step_count)
        return spec
