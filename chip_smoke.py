#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # on a TPU; exits non-zero anywhere else

One process, no children, nothing read that git would not commit, weights
from seeds. It drives the two main paths once through the entry points a
user calls, at the full width of a model each supports, depth cut:

  serving   ``build_server_from_spec`` (RecoverableServer over
            SpeculativeEngine over TokenServingModel over
            FusedMultiTransformer) at GPT-3 6.7B widths, 4 layers: eight
            seeded requests to FINISHED through the packed ragged step and
            the Mosaic paged-attention kernel, then one request's logits
            against the cache-free forward of the same core.
  kernels   every kernel in ``paddle_tpu/ops/pallas`` compiled once at a
            production shape against its jnp reference.
  trainer   ``LlamaSpmdTrainer`` with the settings of
            ``benchmark/configs/mistral-7b-1chip.json`` (Llama-2-7B
            widths, 2 layers, b16 x s2048, bf16): five steps on one
            repeated batch, loss finite and falling, flash kernel in the
            lowered step.
  4 chips   engages when four devices are present: the trainer on a
            pp=2 x mp=2 mesh against the one-chip loss of the same seed,
            and the server with ``mp=4`` shards on four distinct devices.

No rate, MFU or tokens/s is printed: this script establishes that the path
runs. Every phase failure propagates to a non-zero exit. The last line of
stdout is one JSON object, ``{"ok": true, "device": {...}}``.

The phases are importable functions that take sizes, so
``tests/test_chip_smoke.py`` rehearses them tiny on the CPU mesh while
``python chip_smoke.py`` itself stays chip-only.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import re
import tempfile
import time

# device kinds this script has passed on (substring of jax's device_kind)
KNOWN_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")

# ---------------------------------------------------------------------------
# Tolerances, each with its reason.
#
# LOGITS_TOL — engine logits vs the cache-free float32 forward run under
# jax.default_matmul_precision("highest"), as ||engine - ref||_2 / ||ref||_2
# per position over the vocabulary. The engine runs its float32 matmuls at
# the TPU's default precision (one bf16 pass: operands rounded to 8 bits of
# mantissa, relative error 2^-9 per product, accumulated in float32) and
# keeps K/V in a bfloat16 pool (the same 2^-9 rounding once more), through 4
# layers and a 4096-deep readout: a few 1e-3 of the row norm is rounding. A
# wrong page, position or mask replaces a whole attention output, which
# moves the residual stream by O(1) per element against a norm of ~3 per
# element: tens of percent. 3e-2 sits a decade from each.
LOGITS_TOL = 3e-2
# On the CPU rehearsal the pool is float32 and conftest pins matmuls to
# "highest": engine and reference differ by summation order only.
LOGITS_TOL_CPU = 1e-4

# KERNEL_TOL — a Pallas kernel vs its jnp reference run at "highest", as
# max|kernel - ref| / max|ref|. In-kernel float32 dots run on the MXU in bf16
# passes, so 2^-8 of the output scale bounds a correct kernel; a wrong block
# index or mask is O(1).
KERNEL_TOL = 1.5e-2

# FOUR_CHIP_LOSS_TOL — first-step loss of the pp2 x mp2 trainer vs the
# one-chip loss of the same seed, absolute, in nats. Both run bf16; mp=2
# splits each row-parallel contraction in two and rounds each half to bf16
# before the all-reduce, so per-token losses move by ~1e-2 with random sign,
# and the mean over 32k tokens by ~1e-4. A dropped layer, stage or
# all-reduce re-draws the logits of a randomly initialised model: ~1e-2.
FOUR_CHIP_LOSS_TOL = 2e-3


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# device gate and per-phase compile accounting
# ---------------------------------------------------------------------------

def require_tpu() -> dict:
    """Assert the platform is a TPU this script knows; print what jax
    reports. Without a chip this exits non-zero — there is no CPU
    continuation."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU — jax {jax.__version__} found "
            f"platform={dev.platform!r} ({dev.device_kind!r}); this script "
            f"proves the chip path and does not continue on the CPU")
    if not any(k in dev.device_kind for k in KNOWN_DEVICE_KINDS):
        raise SystemExit(
            f"chip_smoke: unknown device_kind {dev.device_kind!r}; known: "
            f"{KNOWN_DEVICE_KINDS} — add it after checking the sizes fit")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    log(f"platform={info['platform']} device_kind={info['kind']!r} "
        f"device_count={info['count']} jax {jax.__version__}")
    return info


class CompileMeter:
    """Sums jax's own backend-compile durations and persistent-cache
    hit/miss counts, so each phase can print its compile portion apart
    from the rest. One instance per process (listeners cannot be
    removed); ``take()`` returns and clears the running totals."""

    def __init__(self):
        import jax
        self._secs = 0.0
        self._n = 0
        self._hits = 0
        self._misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self._secs += duration
            self._n += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._misses += 1

    def take(self) -> dict:
        out = {"compile_s": round(self._secs, 2), "compiles": self._n,
               "cache_hits": self._hits, "cache_misses": self._misses}
        self._secs, self._n, self._hits, self._misses = 0.0, 0, 0, 0
        return out


def _phase_line(name: str, wall: float, comp: dict) -> None:
    # compile is jax's backend-compile time summed over programs; on
    # several devices compiles overlap and the sum can exceed the wall
    rest = wall - comp["compile_s"]
    log(f"[{name}] wall={wall:.1f}s compile={comp['compile_s']:.1f}s "
        f"({comp['compiles']} programs, persistent cache "
        f"{comp['cache_hits']} hit / {comp['cache_misses']} miss)"
        + (f" rest={rest:.1f}s" if rest >= 0 else ""))


def _free_device_memory(after: str) -> None:
    """Drop what the finished phase left behind and say what is still
    held: the next phase needs the chip's memory, and a phase that leaks
    its weights should be loud here, not an OOM somewhere later."""
    import jax
    gc.collect()
    for dev in jax.devices():
        held = _bytes_in_use(dev)
        if held > 2**30:
            top = sorted((a for a in jax.live_arrays()
                          if dev in a.devices()),
                         key=lambda a: -a.nbytes)[:6]
            log(f"[memory] after {after}: device {dev.id} still holds "
                f"{held / 2**30:.2f} GiB; largest live arrays: "
                f"{[(tuple(a.shape), str(a.dtype)) for a in top]}")


def _bytes_in_use(dev) -> int:
    stats = dev.memory_stats()
    return int(stats["bytes_in_use"]) if stats else 0


def _on_devices(arr, expect) -> None:
    got = {d.id for d in arr.devices()}
    want = {d.id for d in expect}
    if got != want:
        raise AssertionError(f"output on devices {sorted(got)}, expected "
                             f"{sorted(want)}")


def _kernel_module(name: str):
    # by module path: the package re-exports functions under the names of
    # two of its own submodules
    import importlib
    return importlib.import_module(f"paddle_tpu.ops.pallas.{name}")


def _lowers_to_mosaic(fn, *args) -> bool:
    """Does ``jit(fn)`` at these shapes lower to the Mosaic custom call
    (a compiled kernel) rather than an interpreted kernel body?"""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


# ---------------------------------------------------------------------------
# serving phase
# ---------------------------------------------------------------------------

def _report_largest_op(label: str, summary: dict, pool_shapes) -> tuple:
    """Log the device operation that took the most time in a traced
    stretch (``benchmark.xplane.summarize``) and its share of the device's
    busy time; a copy or transpose of a whole K/V pool among the
    operations fails the smoke (the row scatter's two layout copies a
    layer were two thirds of serving's device time before PR 29).
    Returns (name, share in %)."""
    ops = summary["op_seconds"]
    name, secs = max(ops.items(), key=lambda kv: kv[1])
    share = 100.0 * secs / summary["busy_s"]
    log(f"[{label}] largest device operation: {name} {secs:.3f} s, "
        f"{share:.1f} % of {summary['busy_s']:.3f} s busy in a window of "
        f"{summary['window_s']:.3f} s")
    for shape in pool_shapes:
        dims = "_".join(str(int(d)) for d in shape)
        back = sorted(k for k in ops
                      if re.fullmatch(rf"(copy|transpose)_\w+_{dims}_", k))
        if back:
            raise AssertionError(
                f"a pool-sized copy is back among the device operations: "
                f"{[(k, round(ops[k], 4)) for k in back]}")
    return name, share


@contextlib.contextmanager
def _device_ops_traced(label: str, pool_shapes):
    """Profile the block on the chip and report its largest device
    operation. A CPU profile has no device plane to read: the rehearsal
    runs the block as it is."""
    import jax
    if jax.devices()[0].platform != "tpu":
        yield
        return
    from benchmark import xplane
    with tempfile.TemporaryDirectory(prefix="chip_smoke_trace_") as outdir:
        tracer = xplane.Tracer(outdir, seconds=0.0)
        tracer.start()
        try:
            with tracer.span(xplane.SPAN_PREFIX + "step"):
                yield
        finally:
            tracer.tick(0, last=True)
            # the collector the server installed for this profile stays
            # readable after the server closes and holds the model (its
            # registry scrapes the core): hand the slot to an empty one,
            # or the phase's weights outlive the phase
            from paddle_tpu.inference import telemetry
            telemetry.open_session_collector()
        _report_largest_op(label, tracer.summary(), pool_shapes)


def _make_prompts(n, lo, hi, shared_prefix, vocab, seed):
    """n seeded prompts of lo..hi tokens; the last two share their first
    ``shared_prefix`` tokens (the prefix cache's customer)."""
    import numpy as np
    rng = np.random.RandomState(seed)
    lens = rng.randint(lo, hi + 1, size=n)
    lens[0], lens[-1] = lo, hi          # both ends of the range, always
    prompts = [rng.randint(0, vocab, size=int(t)).tolist() for t in lens]
    if shared_prefix and n >= 2:
        head = prompts[-2][:shared_prefix]
        prompts[-1][:len(head)] = head
    return prompts


def _slot_of(server, rid: int) -> int:
    # the engine keeps rid -> slot in its stream table; no public accessor
    return server.engine._by_rid[rid].slot


def _serve_requests(server, prompts, *, new_tokens, step_limit):
    """Submit the prompts, step until each has ``new_tokens`` generated,
    release it, drain its outcome. The LAST prompt shares its prefix with
    the one before it and arrives once that one has prefilled, so it
    adopts the shared pages and streams the rest of its prompt beside the
    others' decode rows; it is the probe whose logits are captured.
    Returns (rids, streams, outcomes, steps, probe logits)."""
    tsm = server.engine.target
    tap = []                      # logits the engine samples from, in order
    inner_logits = tsm.logits

    def tapped_logits(hidden):
        out = inner_logits(hidden)
        tap.append(out.data)
        return out
    tsm.logits = tapped_logits
    try:
        rids = [server.submit(p) for p in prompts[:-1]]
        mate, probe = rids[-1], None
        probe_logits = []         # last prompt position, then decode rows
        live = set(rids)
        streams = {}              # rid -> generated tokens, at release
        outcomes = []
        steps = 0
        while live:
            if steps >= step_limit:
                raise AssertionError(f"requests {sorted(live)} still live "
                                     f"after {steps} steps")
            if probe is None and (mate not in live
                                  or server.generated(mate)):
                probe = server.submit(prompts[-1])
                rids.append(probe)
                live.add(probe)
            before = len(server.generated(probe)) if probe in live else -1
            del tap[:]
            emitted = server.step()
            steps += 1
            if before == 0 and server.generated(probe):
                # the probe was admitted in this step: admissions sample
                # in completion order and it is the youngest, so its
                # readout is the last [1, vocab] call
                probe_logits.append([a for a in tap if a.ndim == 2][-1][0])
            elif probe in emitted and 0 < len(probe_logits) < 5:
                verify = [a for a in tap if a.ndim == 3][-1]
                probe_logits.append(verify[_slot_of(server, probe), 0])
            for rid in sorted(live):
                gen = server.generated(rid)
                if len(gen) >= new_tokens:
                    streams[rid] = gen
                    server.release(rid)
                    live.discard(rid)
            outcomes.extend(server.drain_outcomes())
        outcomes.extend(server.drain_outcomes())
    finally:
        tsm.logits = inner_logits
    return rids, streams, outcomes, steps, probe_logits


def _check_probe_logits(tsm, prompt, gen, probe_logits, tol) -> float:
    """The engine's logits at the probe's last prompt position and first
    four decode positions against the cache-free forward of the same core
    over the whole sequence (teacher-forced with the engine's tokens)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.framework.autograd import no_grad
    from paddle_tpu.framework.tensor import Tensor

    if len(probe_logits) < 5:
        raise AssertionError(f"captured {len(probe_logits)} of the probe's "
                             f"5 logit rows")
    T = len(prompt)
    seq = prompt + gen[:4]
    n = len(seq)
    core = getattr(tsm.core, "base", tsm.core)   # the float core
    mask = np.where(np.arange(n)[None, :] <= np.arange(n)[:, None],
                    0.0, -1e30).astype(np.float32)
    # no_grad: the dygraph tape would otherwise keep the weights and this
    # forward's activations alive after the phase returns
    with jax.default_matmul_precision("highest"), no_grad():
        hidden = core(Tensor(jnp.asarray(tsm.embed(seq)[None])),
                      attn_mask=Tensor(jnp.asarray(mask)))
        ref = np.asarray(tsm.logits(hidden).numpy())[0]
    errs = []
    for i, g in enumerate(np.asarray(a) for a in probe_logits):
        r = ref[T - 1 + i]
        if g.shape != r.shape or not np.isfinite(g).all():
            raise AssertionError(f"probe logits {i}: shape {g.shape}, "
                                 f"finite={np.isfinite(g).all()}")
        if int(g.argmax()) != gen[i]:
            raise AssertionError(
                f"position {T - 1 + i}: captured logits argmax "
                f"{int(g.argmax())} is not the emitted token {gen[i]}")
        errs.append(float(np.linalg.norm(g - r) / np.linalg.norm(r)))
    log(f"[serving] probe request (prompt {T} tokens): logits at last "
        f"prompt position + 4 decode positions vs cache-free forward, "
        f"rel. L2 error {['%.2e' % e for e in errs]} (tol {tol:g})")
    if max(errs) > tol:
        raise AssertionError(f"logits disagree with the cache-free "
                             f"forward: {errs} > {tol}")
    return max(errs)


def serving_phase(*, d_model=4096, heads=32, ffn=16384, layers=4,
                  vocab=50257, block_size=16, num_blocks=4096,
                  max_blocks_per_seq=40, max_batch=8, n_requests=8,
                  prompt_lo=64, prompt_hi=512, shared_prefix=128,
                  new_tokens=32, prefill_token_budget=256,
                  kv_dtype="bfloat16", mp=1, expect_kernel=True,
                  logits_tol=LOGITS_TOL, seed=0, meter=None) -> dict:
    """Serve ``n_requests`` seeded requests to FINISHED through the
    server both transports build, then check one request's logits
    against the cache-free forward. Returns counters; raises on any
    failed check."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework import device
    from paddle_tpu.inference.resilience import RequestOutcome
    from paddle_tpu.inference.router import build_server_from_spec
    pa = _kernel_module("paged_attention")

    if new_tokens < 5 or n_requests < 2:
        raise ValueError("the logits check reads the probe's admission and "
                         "4 decode rows, and the probe needs a prefix mate: "
                         "new_tokens >= 5, n_requests >= 2")
    t_phase = time.perf_counter()
    devs = jax.devices()
    base = [_bytes_in_use(d) for d in devs[:mp]]
    spec = {
        "d_model": d_model, "heads": heads, "ffn": ffn, "layers": layers,
        "vocab": vocab, "model_seed": seed, "embed_seed": 1234 + seed,
        # walk the vocabulary instead of collapsing to the tied readout's
        # fixed point: a constant stream would hide a wrong handoff
        "head_roll": 1, "mp": mp, "k": 0, "max_batch": max_batch,
        "block_size": block_size, "num_blocks": num_blocks,
        "max_blocks_per_seq": max_blocks_per_seq, "prefix_cache": True,
        "prefill_token_budget": prefill_token_budget, "kv_dtype": kv_dtype,
    }
    prompts = _make_prompts(n_requests, prompt_lo, prompt_hi, shared_prefix,
                            vocab, seed)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        server = build_server_from_spec(dict(
            spec, journal_path=os.path.join(workdir, "journal.wal"),
            snapshot_path=os.path.join(workdir, "snapshot.bin")))
        try:
            eng = server.engine.engine            # PagedServingEngine
            tsm = server.engine.target            # TokenServingModel
            cache = eng.cache
            pool_dtype = str(cache.pools[0].data.dtype)
            log(f"[serving] server built: {layers} layers d={d_model} "
                f"heads={heads} ffn={ffn} vocab={vocab} mp={mp}; pool "
                f"{num_blocks} blocks x {block_size} dtype={pool_dtype} "
                f"({cache.pool_bytes_total() / 2**30:.2f} GiB over {mp} "
                f"device(s))")
            if pool_dtype != kv_dtype:
                raise AssertionError(f"pool dtype {pool_dtype}, asked for "
                                     f"{kv_dtype}")
            if not eng._ragged_active():
                raise AssertionError("the packed ragged step is not active")
            # at mp = 1 every attention launch is the Pallas kernel; the
            # compiled mp >= 2 program attends with gather + sdpa inside
            # the one jitted step (ROADMAP S2) and never enters the
            # kernel wrapper
            kernel_on_path = mp == 1 and device.use_pallas_kernels()
            if expect_kernel and mp == 1 and not kernel_on_path:
                raise AssertionError("the paged-attention kernel is not on "
                                     "the engine's path")

            pa.reset_dispatch_count()
            step_limit = 64 + 4 * (sum(map(len, prompts))
                                   // prefill_token_budget + new_tokens)
            # the compiled mp >= 2 step still scatters rows inside its
            # shard_map and pays the two copies (ROADMAP S6): reported
            # there, held to nothing
            with _device_ops_traced(
                    f"serving mp={mp}",
                    {tuple(p.shape) for p in cache.pools} if mp == 1
                    else ()):
                rids, streams, outcomes, steps, probe_logits = \
                    _serve_requests(server, prompts, new_tokens=new_tokens,
                                    step_limit=step_limit)
            jax.block_until_ready(cache.pools[0].data)
            serve_wall = time.perf_counter() - t_phase

            # -- all requests FINISHED, streams in range ------------------
            status = {oc.rid: oc.status for oc in outcomes}
            bad = {r: status.get(r) for r in rids
                   if status.get(r) != RequestOutcome.FINISHED}
            if bad:
                raise AssertionError(f"requests not FINISHED: {bad}")
            for rid in rids:
                gen = streams[rid]
                if len(gen) < new_tokens or \
                        not all(0 <= t < vocab for t in gen):
                    raise AssertionError(f"request {rid}: bad stream "
                                         f"{gen[:8]}…")
            st = eng.prefill_stats
            packed_steps = st.prefill_steps   # each plans one packed launch
            log(f"[serving] {len(rids)} requests FINISHED in {steps} steps: "
                f"packed steps={packed_steps} (mixed prefill+decode="
                f"{st.mixed_steps}), decode steps={st.decode_steps}, prefix "
                f"tokens skipped={eng.prefix_stats.tokens_skipped}, "
                f"paged-attention launches traced={pa.dispatch_count()}")
            if packed_steps <= 0 or st.mixed_steps <= 0:
                raise AssertionError("no packed (mixed) step was taken")
            if kernel_on_path and pa.dispatch_count() <= 0:
                raise AssertionError("paged_attention_ragged never launched")
            if shared_prefix and eng.prefix_stats.tokens_skipped <= 0:
                raise AssertionError("the shared prefix was never adopted")

            # -- where the pool lives -------------------------------------
            if mp > 1:
                from paddle_tpu.parallel.mesh import serving_mesh
                if serving_mesh(mp, tsm.core.shard_devices) is None:
                    raise AssertionError(
                        f"serving_mesh({mp}) is None: the shards do not "
                        f"sit on {mp} distinct devices")
                ids = sorted(d.id for i in range(mp)
                             for d in cache.pools[i].data.devices())
                if len(set(ids)) != mp:
                    raise AssertionError(f"pool shards on devices {ids}")
                log(f"[serving] compiled mp step: "
                    f"{tsm.core.sharded_metrics()}")
                share = cache.pool_bytes()
                used = [_bytes_in_use(d) - b
                        for d, b in zip(devs[:mp], base)]
                log(f"[serving] bytes_in_use per device since phase start: "
                    f"{[round(u / 2**30, 2) for u in used]} GiB; pool "
                    f"share {share / 2**30:.2f} GiB each")
                if devs[0].memory_stats() and min(used) < share:
                    raise AssertionError(f"a device holds less than its "
                                         f"pool share ({share} B): {used}")
            else:
                _on_devices(cache.pools[0].data, [devs[0]])

            # -- the kernel really was the compiled one -------------------
            if kernel_on_path and expect_kernel:
                B = max_batch
                q = jnp.zeros((B, heads, d_model // heads), jnp.float32)
                if not _lowers_to_mosaic(
                        lambda q_, p_, bt_, kl_: pa.paged_attention_ragged(
                            q_, p_, bt_, (1,) * B, kl_, tile_q=1),
                        q, cache.pools[0].data, cache.bt_tensor().data,
                        jnp.ones((B,), jnp.int32)):
                    raise AssertionError(
                        "paged_attention_ragged at the decode step's shape "
                        "does not lower to Mosaic")

            # -- logits vs the cache-free forward (outside any timing) ----
            err = _check_probe_logits(tsm, prompts[-1], streams[rids[-1]],
                                      probe_logits, logits_tol)
            server.check_invariants()
        finally:
            server.close()
    comp = meter.take() if meter is not None else None
    if comp is not None:
        _phase_line(f"serving mp={mp}", time.perf_counter() - t_phase, comp)
    return {"steps": steps, "packed_steps": packed_steps,
            "mixed_steps": st.mixed_steps, "serve_wall_s": serve_wall,
            "logits_rel_err": err, "pool_dtype": pool_dtype}


# ---------------------------------------------------------------------------
# kernel phase: every kernel in ops/pallas at one production shape
# ---------------------------------------------------------------------------

def _rel_err(out, ref) -> float:
    import jax
    import numpy as np
    worst = 0.0
    for o, r in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        o = np.asarray(o, np.float32)
        r = np.asarray(r, np.float32)
        if o.shape != r.shape:
            raise AssertionError(f"shape {o.shape} != reference {r.shape}")
        if not np.isfinite(o).all():
            raise AssertionError("non-finite kernel output")
        worst = max(worst, float(np.abs(o - r).max()
                                 / max(np.abs(r).max(), 1e-30)))
    return worst


def _run_check(name, shape, fn, ref_fn, args, expect_kernel, tol) -> dict:
    """Compile ``fn`` at ``args``, run it, compare with ``ref_fn`` run at
    the highest matmul precision. Raises with the compiler's message if
    Mosaic refuses the kernel."""
    import jax
    lowered = jax.jit(fn).lower(*args)
    mosaic = "tpu_custom_call" in lowered.as_text()
    out = lowered.compile()(*args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_fn)(*args)
    err = _rel_err(out, ref)
    log(f"[kernels] {name:<28} {shape:<34} mosaic={mosaic} "
        f"err={err:.2e}")
    if expect_kernel and not mosaic:
        raise AssertionError(f"{name}: no Mosaic custom call in the "
                             f"lowered program (interpreted body?)")
    if err > tol:
        raise AssertionError(f"{name}: error {err:.3e} > {tol}")
    return {"kernel": name, "shape": shape, "mosaic": mosaic, "err": err}


def _paged_inputs(rng, q_lens, kv_lens, *, heads, head_dim, block_size,
                  num_blocks, max_blocks, pool_dtype):
    """A random pool and disjoint block tables for a ragged batch."""
    import jax.numpy as jnp
    import numpy as np
    R = sum(q_lens)
    q = jnp.asarray(rng.standard_normal((R, heads, head_dim)), jnp.float32)
    pool = rng.standard_normal(
        (num_blocks, 2, heads, block_size, head_dim)).astype(np.float32)
    bt = np.zeros((len(q_lens), max_blocks), np.int32)
    perm = rng.permutation(np.arange(1, num_blocks))
    used = 0
    for s, kl in enumerate(kv_lens):
        nb = -(-int(kl) // block_size)
        bt[s, :nb] = perm[used:used + nb]
        used += nb
    return (q, jnp.asarray(pool, pool_dtype), jnp.asarray(bt),
            jnp.asarray(kv_lens, jnp.int32))


def kernel_phase(*, heads=32, head_dim=128, block_size=16, num_blocks=512,
                 max_blocks=40, batch=8, chunk=256, d_model=4096,
                 vocab=50257, moe_experts=64, moe_d=2048, moe_ffn=1024,
                 norm_rows=8192, flash_seq=2048, flash_heads=4,
                 adam_shape=(4096, 2752), pool_dtype="bfloat16",
                 expect_kernel=True, tol=KERNEL_TOL, seed=0,
                 meter=None) -> list:
    """Compile every kernel in ``paddle_tpu/ops/pallas`` once at one
    production shape and compare it with its jnp reference. Returns the
    per-kernel table; raises on the first kernel Mosaic refuses or that
    disagrees (the compiler's message is the exception)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.inference.paged_cache import _quant_rows
    da, fa, fused_adamw, fused_norm, grouped_gemm, int8_matmul, pa = map(
        _kernel_module, ("decode_attention", "flash_attention",
                         "fused_adamw", "fused_norm", "grouped_gemm",
                         "int8_matmul", "paged_attention"))

    t_phase = time.perf_counter()
    rng = np.random.default_rng(seed)
    rows = []
    cap = max_blocks * block_size
    geo = dict(heads=heads, head_dim=head_dim, block_size=block_size,
               num_blocks=num_blocks, max_blocks=max_blocks,
               pool_dtype=pool_dtype)

    def check(name, shape, fn, ref_fn, args, tol_=tol):
        rows.append(_run_check(name, shape, fn, ref_fn, args,
                               expect_kernel, tol_))

    # -- the ragged paged-attention kernel at the serving step's shapes ----
    a = chunk // 2 + chunk // 8 + 1      # neither a whole number of tiles
    b = chunk - a
    ragged = {
        # decode rows: tile_q = 1, MHA, so one query row per grid step
        "decode": ((1,) * batch,
                   rng.integers(block_size + 1, cap, size=batch), 1),
        # one prefill chunk, partway into its prompt
        "prefill": ((chunk,), [chunk + 3 * block_size + 5], None),
        # one mixed packed launch: two chunks (one a partial tile) + decodes
        "mixed": ((a, b) + (1,) * batch,
                  [a + block_size, b]
                  + list(rng.integers(2, cap, size=batch)), None),
    }
    for kind, (q_lens, kv_lens, tile_q) in ragged.items():
        q, pool, bt, kvl = _paged_inputs(rng, q_lens, kv_lens, **geo)
        check(f"paged_ragged/{kind}",
              f"R={sum(q_lens)} nh={heads} hd={head_dim} {pool.dtype}",
              lambda q_, p_, bt_, kl_, ql=q_lens, tq=tile_q:
              pa.paged_attention_ragged(q_, p_, bt_, ql, kl_, tile_q=tq),
              lambda q_, p_, bt_, kl_, ql=q_lens:
              pa.paged_attention_ragged_reference(q_, p_, bt_, ql, kl_),
              (q, pool, bt, kvl))

    # -- its int8-page twin (engine option kv_dtype="int8") ---------------
    q_lens, kv_lens, _ = ragged["mixed"]
    q, pool, bt, kvl = _paged_inputs(rng, q_lens, kv_lens,
                                     **dict(geo, pool_dtype="float32"))
    pool_q, scales = _quant_rows(pool)
    check("paged_ragged_int8/mixed",
          f"R={sum(q_lens)} nh={heads} hd={head_dim} int8+scales",
          lambda q_, p_, sc_, bt_, kl_: pa.paged_attention_ragged(
              q_, p_, bt_, q_lens, kl_, kv_scales=sc_),
          lambda q_, p_, sc_, bt_, kl_: pa.paged_attention_ragged_reference(
              q_, p_, bt_, q_lens, kl_, kv_scales=sc_),
          (q, pool_q, scales, bt, kvl))

    # -- dense-cache flash-decoding ----------------------------------------
    S = cap
    q = jnp.asarray(rng.standard_normal((batch, heads, head_dim)),
                    jnp.float32)
    kc = jnp.asarray(rng.standard_normal((batch, S, heads, head_dim)),
                     pool_dtype)
    vc = jnp.asarray(rng.standard_normal((batch, S, heads, head_dim)),
                     pool_dtype)
    lens = jnp.asarray(rng.integers(1, S, size=batch), jnp.int32)
    check("decode_attention", f"B={batch} S={S} nh={heads} hd={head_dim}",
          da.decode_attention, da.decode_attention_reference,
          (q, kc, vc, lens))

    # -- grouped GEMM at the MoE serving core's block_m = 8 ---------------
    block_m = 8
    M = moe_experts * block_m
    lhs = jnp.asarray(rng.standard_normal((M, moe_d)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((moe_experts, moe_d, moe_ffn))
                      * moe_d ** -0.5, jnp.float32)
    be = jnp.asarray(rng.permutation(moe_experts), jnp.int32)
    check("grouped_gemm/block_m=8",
          f"M={M} K={moe_d} N={moe_ffn} E={moe_experts} f32",
          lambda l, r, b: grouped_gemm.gmm(l, r, b, block_m=block_m),
          lambda l, r, b: grouped_gemm.gmm_reference(l, r, b,
                                                     block_m=block_m),
          (lhs, rhs, be))

    # -- w8a16 on the readout (engine option weight_dtype="int8") ---------
    x = jnp.asarray(rng.standard_normal((batch, d_model)), jnp.float32)
    w8 = jnp.asarray(rng.integers(-127, 128, size=(d_model, vocab)),
                     jnp.int8)

    def w8a16(x_, w_):
        out = int8_matmul.w8a16_matmul(x_, w_)
        if out is None:
            raise AssertionError("w8a16_matmul declined the readout shape")
        return out
    check("w8a16_matmul/readout", f"M={batch} K={d_model} N={vocab}",
          w8a16, lambda x_, w_: x_ @ w_.astype(jnp.float32), (x, w8))

    # -- fused norms (+ residual) and fused dropout ------------------------
    xn = jnp.asarray(rng.standard_normal((norm_rows, d_model)), jnp.bfloat16)
    rn = jnp.asarray(rng.standard_normal((norm_rows, d_model)), jnp.bfloat16)
    wn = jnp.asarray(1 + 0.1 * rng.standard_normal(d_model), jnp.bfloat16)
    bn = jnp.asarray(0.1 * rng.standard_normal(d_model), jnp.bfloat16)

    def rms_ref(x_, r_, w_):
        z = x_.astype(jnp.float32) + r_.astype(jnp.float32)
        y = z * jax.lax.rsqrt(jnp.mean(z * z, -1, keepdims=True) + 1e-6)
        return (y * w_.astype(jnp.float32)).astype(x_.dtype), \
            z.astype(x_.dtype)

    def ln_ref(x_, r_, w_, b_):
        z = x_.astype(jnp.float32) + r_.astype(jnp.float32)
        zc = z - jnp.mean(z, -1, keepdims=True)
        y = zc * jax.lax.rsqrt(jnp.mean(zc * zc, -1, keepdims=True) + 1e-5)
        return (y * w_.astype(jnp.float32) + b_.astype(jnp.float32)
                ).astype(x_.dtype), z.astype(x_.dtype)
    nshape = f"[{norm_rows}, {d_model}] bf16"
    check("fused_rms_norm_residual", nshape,
          fused_norm.fused_rms_norm_residual, rms_ref, (xn, rn, wn))
    check("fused_layer_norm_residual", nshape,
          fused_norm.fused_layer_norm_residual, ln_ref, (xn, rn, wn, bn))
    check("fused_rms_norm", nshape, fused_norm.fused_rms_norm,
          lambda x_, w_: rms_ref(x_, jnp.zeros_like(x_), w_)[0], (xn, wn))
    check("fused_layer_norm", nshape, fused_norm.fused_layer_norm,
          lambda x_, w_, b_: ln_ref(x_, jnp.zeros_like(x_), w_, b_)[0],
          (xn, wn, bn))
    if expect_kernel:
        # the on-core PRNG has no interpret twin and no bitwise reference:
        # kept values are x / (1 - p) exactly, and about 1 - p are kept
        rate = 0.25
        drop = jax.jit(lambda x_: fused_norm._fused_dropout(x_, rate, 7))
        if not _lowers_to_mosaic(
                lambda x_: fused_norm._fused_dropout(x_, rate, 7), xn):
            raise AssertionError("fused dropout does not lower to Mosaic")
        y = np.asarray(drop(xn), np.float32)
        kept = y != 0
        frac = float(kept.mean())
        scaled = np.asarray(xn, np.float32) / (1 - rate)
        if abs(frac - (1 - rate)) > 0.01 or \
                not np.allclose(y[kept], scaled[kept], rtol=1e-2):
            raise AssertionError(f"fused dropout: kept {frac:.4f}")
        log(f"[kernels] {'fused_dropout':<28} {nshape:<34} mosaic=True "
            f"kept={frac:.4f}")
        rows.append({"kernel": "fused_dropout", "shape": nshape,
                     "mosaic": True, "err": abs(frac - (1 - rate))})

    # -- fused AdamW --------------------------------------------------------
    p = jnp.asarray(rng.standard_normal(adam_shape) * 0.02, jnp.bfloat16)
    g = jnp.asarray(rng.standard_normal(adam_shape) * 0.01, jnp.bfloat16)
    m = jnp.asarray(rng.standard_normal(adam_shape) * 0.01, jnp.float32)
    v = jnp.asarray(rng.random(adam_shape) * 1e-4, jnp.float32)
    master = p.astype(jnp.float32)
    hp = dict(lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8, wd=0.1, step=3.0)

    def adam_ref(p_, g_, m_, v_, ma_):
        g32 = g_.astype(jnp.float32)
        m2 = hp["beta1"] * m_ + (1 - hp["beta1"]) * g32
        v2 = hp["beta2"] * v_ + (1 - hp["beta2"]) * g32 * g32
        mh = m2 / (1 - hp["beta1"] ** hp["step"])
        vh = v2 / (1 - hp["beta2"] ** hp["step"])
        ma2 = ma_ - hp["lr"] * (mh / (jnp.sqrt(vh) + hp["eps"])
                                + hp["wd"] * ma_)
        return ma2.astype(p_.dtype), m2, v2, ma2
    check("fused_adamw", f"{list(adam_shape)} bf16 param, f32 state",
          lambda *a: fused_adamw.fused_adamw_update(*a, **hp), adam_ref,
          (p, g, m, v, master))

    # -- flash attention, forward + backward -------------------------------
    # jax's tuned kernel with this repo's block sizes (the trainer's path)
    # and the repo's own kernel (dropout / cross-length causal path)
    def mk():
        return jnp.asarray(rng.standard_normal(
            (1, flash_seq, flash_heads, head_dim)), jnp.bfloat16)
    fq, fk, fv = mk(), mk(), mk()

    def flash_loss(impl):
        def f(q_, k_, v_):
            if impl == "native":
                out = fa._native_flash_bhtd(
                    jnp.moveaxis(q_, 1, 2), jnp.moveaxis(k_, 1, 2),
                    jnp.moveaxis(v_, 1, 2), jnp.int32(0), True,
                    head_dim ** -0.5, 0.0)
                out = jnp.moveaxis(out, 1, 2)
            elif impl == "tuned":
                out = fa.flash_attention_blhd(q_, k_, v_, causal=True)
            else:
                out = jnp.moveaxis(fa._mha_jnp(
                    jnp.moveaxis(q_, 1, 2).astype(jnp.float32),
                    jnp.moveaxis(k_, 1, 2).astype(jnp.float32),
                    jnp.moveaxis(v_, 1, 2).astype(jnp.float32), True,
                    head_dim ** -0.5), 1, 2)
            return out.astype(jnp.float32)

        def fwd_bwd(q_, k_, v_):
            out, vjp = jax.vjp(f, q_, k_, v_)
            cot = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                          ).reshape(out.shape)
            return (out,) + vjp(cot)
        return fwd_bwd
    fshape = f"[1, {flash_seq}, {flash_heads}, {head_dim}] bf16 causal"
    if expect_kernel:      # jax's tuned kernel has no interpret mode
        check("flash_attention/tuned fwd+bwd", fshape, flash_loss("tuned"),
              flash_loss("ref"), (fq, fk, fv), tol_=3e-2)
    check("flash_attention/native fwd+bwd", fshape, flash_loss("native"),
          flash_loss("ref"), (fq, fk, fv), tol_=3e-2)

    comp = meter.take() if meter is not None else None
    if comp is not None:
        _phase_line("kernels", time.perf_counter() - t_phase, comp)
    return rows


# ---------------------------------------------------------------------------
# trainer phases
# ---------------------------------------------------------------------------

def _make_trainer(*, hidden, inter, heads, vocab, layers, seq, dtype, seed,
                  n_micro=None):
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.llama_spmd import LlamaSpmdTrainer
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                      intermediate_size=inter, num_hidden_layers=layers,
                      num_attention_heads=heads, num_key_value_heads=heads,
                      max_position_embeddings=seq)
    dt = getattr(jnp, dtype)
    # the settings of benchmark/configs/mistral-7b-1chip.json: no remat,
    # bf16 moments, unrolled layer scan
    return LlamaSpmdTrainer(cfg, compute_dtype=dt, remat=False,
                            remat_policy="full", moments_dtype=dt,
                            scan_unroll=2, seed=seed, n_micro=n_micro)


def _step_lowers_to_mosaic(trainer, ids) -> bool:
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import mesh as mesh_mod
    ids = mesh_mod.shard_tensor_data(jnp.asarray(ids), P("dp", None))
    txt = trainer._step_fn.lower(
        trainer.params, trainer.opt_state, ids, ids,
        jnp.asarray(trainer.lr, jnp.float32),
        jnp.asarray(1.0, jnp.float32)).as_text()
    return "tpu_custom_call" in txt


def trainer_phase(*, hidden=4096, inter=11008, heads=32, vocab=32000,
                  layers=2, batch=16, seq=2048, steps=5, dtype="bfloat16",
                  expect_kernel=True, seed=0, meter=None) -> dict:
    """``LlamaSpmdTrainer`` on one device: ``steps`` train steps on one
    repeated seeded batch; loss finite and lower at the end."""
    import jax
    import numpy as np
    from paddle_tpu.parallel import mesh as mesh_mod

    t_phase = time.perf_counter()
    dev = jax.devices()[0]
    mesh_mod.build_mesh(dp=1, devices=[dev])
    trainer = _make_trainer(hidden=hidden, inter=inter, heads=heads,
                            vocab=vocab, layers=layers, seq=seq,
                            dtype=dtype, seed=seed)
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, seq))
    t0 = time.perf_counter()
    losses = [float(trainer.train_step(ids))]        # compile + step 1
    first = time.perf_counter() - t0
    losses += [float(trainer.train_step(ids)) for _ in range(steps - 1)]
    log(f"[trainer] {layers} layers h={hidden} ffn={inter} heads={heads} "
        f"vocab={vocab} b{batch} x s{seq} {dtype}: first step (compile "
        f"included) {first:.1f}s; losses "
        f"{[round(l, 4) for l in losses]}")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    _on_devices(jax.tree_util.tree_leaves(trainer.params)[0], [dev])
    mosaic = _step_lowers_to_mosaic(trainer, ids)
    log(f"[trainer] lowered step contains the flash kernel's Mosaic "
        f"custom call: {mosaic}")
    if expect_kernel and not mosaic:
        raise AssertionError("the lowered train step has no Mosaic custom "
                             "call: flash attention is not on the path")
    comp = meter.take() if meter is not None else None
    if comp is not None:
        _phase_line("trainer", time.perf_counter() - t_phase, comp)
    return {"losses": losses, "first_step_s": first, "mosaic": mosaic}


def trainer_four_chip_phase(*, hidden=4096, inter=11008, heads=32,
                            vocab=32000, layers=4, batch=16, seq=2048,
                            steps=3, dtype="bfloat16", expect_kernel=True,
                            loss_tol=FOUR_CHIP_LOSS_TOL, seed=0,
                            meter=None) -> dict:
    """The trainer on a pp=2 x mp=2 mesh of four devices: first-step loss
    equal to the one-chip loss of the same seed (same widths and depth),
    then ``steps`` steps with the loss finite and falling."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.parallel import mesh as mesh_mod

    t_phase = time.perf_counter()
    devs = jax.devices()[:4]
    if len(devs) < 4:
        raise AssertionError(f"needs 4 devices, found {len(devs)}")
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, seq))
    kw = dict(hidden=hidden, inter=inter, heads=heads, vocab=vocab,
              layers=layers, seq=seq, dtype=dtype, seed=seed)

    # one chip, same seed: forward loss only (the optimizer state of the
    # full-depth model does not fit beside its activations on one chip)
    mesh_mod.build_mesh(dp=1, devices=devs[:1])
    ref = _make_trainer(**kw)
    ref.opt_state = None
    gc.collect()
    one_chip = float(jax.jit(ref.loss_fn)(ref.params, jnp.asarray(ids),
                                          jnp.asarray(ids)))
    del ref
    gc.collect()

    mesh_mod.build_mesh(pp=2, mp=2, devices=devs)
    trainer = _make_trainer(**kw)
    losses = [float(trainer.train_step(ids)) for _ in range(steps)]
    delta = abs(losses[0] - one_chip)
    log(f"[trainer pp2 x mp2] {layers} layers b{batch} x s{seq} {dtype} "
        f"on devices {[d.id for d in devs]}: losses "
        f"{[round(l, 4) for l in losses]}; one-chip loss of the same "
        f"seed {one_chip:.4f}, |delta| {delta:.2e} (tol {loss_tol:g})")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if delta > loss_tol:
        raise AssertionError(f"pp2 x mp2 first-step loss {losses[0]} != "
                             f"one-chip {one_chip} (|delta| {delta})")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    _on_devices(trainer.params["blocks"]["wq"], devs)
    mosaic = _step_lowers_to_mosaic(trainer, ids)
    if expect_kernel and not mosaic:
        raise AssertionError("pp2 x mp2 step has no Mosaic custom call")
    comp = meter.take() if meter is not None else None
    if comp is not None:
        _phase_line("trainer pp2 x mp2", time.perf_counter() - t_phase, comp)
    return {"losses": losses, "one_chip_loss": one_chip, "delta": delta}


# ---------------------------------------------------------------------------

# DECODER_LOGITS_TOL — the config-driven decoder core (arch "afmoe") against
# its plain float32 reference, as relative L2 per probed position. Weights
# are bfloat16 (the reference takes the same rounded weights), products
# accumulate in float32 and hand bfloat16 on, K/V pages are bfloat16 and
# the kernel's dot is one bf16 pass: 6e-3 to 7e-3 through five sandwich
# layers (the benchmark's probe, PR 28, my chip runs); a reference with
# its matrices rounded to 3 mantissa bits reads 8e-2. A dropped window, RoPE on a full layer or a capacity drop moves
# the logits by tens of percent; routing that disagrees with the reference
# outside its margin fails the probe by itself.
DECODER_LOGITS_TOL = 2e-2


def decoder_phase(*, hidden=3072, heads=48, kv_heads=8, head_dim=128,
                  window=4096, dense_width=12288, experts=256, held=32,
                  top_k=4, expert_width=3072, vocab=25024, prompt=4608,
                  chunk=512, block_size=16, max_batch=8,
                  weight_dtype="bfloat16", kv_dtype="bfloat16",
                  expect_kernel=True, logits_tol=DECODER_LOGITS_TOL,
                  tol=KERNEL_TOL, seed=0, meter=None) -> dict:
    """The ``afmoe`` core at published widths, one layer of each type: a
    windowed ragged launch at ``heads`` / ``kv_heads`` past ``window``
    positions and the dropless grouped GEMM at ``held`` experts against
    their jnp references, then a two-layer server (a sliding dense layer,
    a full expert layer) through ``build_server_from_spec`` whose probe
    (a prompt past the window in chunks, four decode rows) is held to the
    plain reference's logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import tempfile
    from benchmark.jobs import serve_arch
    gg, pa = _kernel_module("grouped_gemm"), _kernel_module("paged_attention")

    t_phase = time.perf_counter()
    rng = np.random.default_rng(seed)
    rows = []

    # -- the windowed launch: a chunk and decode rows past the window ------
    max_blocks = -(-(prompt + 64) // block_size)
    q_lens = (chunk,) + (1,) * max_batch
    kv_lens = [prompt] + list(rng.integers(2, prompt, size=max_batch - 1)) \
        + [window + block_size + 3]
    num_blocks = 1 + sum(-(-int(n) // block_size) for n in kv_lens)
    _, _, bt, kvl = _paged_inputs(
        rng, q_lens, kv_lens, heads=1, head_dim=1, block_size=block_size,
        num_blocks=num_blocks, max_blocks=max_blocks, pool_dtype="float32")
    q = jnp.asarray(rng.standard_normal((sum(q_lens), heads, head_dim)),
                    jnp.float32)
    pool = jnp.asarray(rng.standard_normal(
        (num_blocks, 2, kv_heads, block_size, head_dim)), kv_dtype)
    for w in (window, None):
        rows.append(_run_check(
            f"paged_ragged/window={w}",
            f"R={sum(q_lens)} nh={heads} nkv={kv_heads} hd={head_dim}",
            lambda q_, p_, bt_, kl_, w=w: pa.paged_attention_ragged(
                q_, p_, bt_, q_lens, kl_, window=w),
            lambda q_, p_, bt_, kl_, w=w: pa.paged_attention_ragged_reference(
                q_, p_, bt_, q_lens, kl_, window=w),
            (q, pool, bt, kvl), expect_kernel, tol))

    # -- the dropless grouped GEMM at the experts held here ----------------
    block_m = 16
    counts = rng.integers(0, 3 * block_m, size=held)
    counts[rng.integers(0, held)] = 0            # an expert without a row
    used = int((-(-counts // block_m)).sum())
    blocks = used + held                         # a worst-case tail
    be = np.repeat(np.arange(held), -(-counts // block_m))
    be = np.concatenate([be, np.full(blocks - used, be[-1])]).astype(np.int32)
    dt = jnp.dtype(weight_dtype)
    lhs = jnp.asarray(rng.standard_normal((blocks * block_m, hidden)), dt)
    rhs = jnp.asarray(rng.standard_normal((held, hidden, 2 * expert_width))
                      * hidden ** -0.5, dt)
    live = used * block_m

    def gmm_ref(l, r, b, n):
        out = jnp.einsum("bmk,bkn->bmn",
                         l.reshape(-1, block_m, l.shape[-1])
                         .astype(jnp.float32), r[b].astype(jnp.float32))
        return out.reshape(l.shape[0], -1)[:live]
    rows.append(_run_check(
        "gmm/blocks_used", f"M={blocks * block_m} K={hidden} "
        f"N={2 * expert_width} E={held} {weight_dtype}",
        lambda l, r, b, n: gg.gmm(l, r, b, block_m=block_m, block_n=512,
                                  block_k=hidden, blocks_used=n)[:live],
        gmm_ref, (lhs, rhs, jnp.asarray(be), jnp.asarray([used], jnp.int32)),
        expect_kernel, tol))

    # -- two layers through the server against the plain reference ---------
    config = {
        "model_type": "afmoe", "reference": "afmoe", "hidden_size": hidden,
        "num_attention_heads": heads, "num_key_value_heads": kv_heads,
        "head_dim": head_dim, "sliding_window": window,
        "intermediate_size": dense_width, "num_experts": held,
        "num_experts_per_tok": top_k, "num_shared_experts": 1,
        "moe_intermediate_size": expert_width, "route_norm": True,
        "route_scale": 2.448, "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "mup_enabled": True, "vocab_size": vocab,
        "weight_dtype": weight_dtype, "num_dense_layers": 1,
        "layer_types": ["sliding_attention", "full_attention"],
        "layers_run": [0, 1],
        "deployment_cut": {"num_experts_published": experts,
                           "expert_offset": held},
        "engine": {"mp": 1, "k": 0, "max_batch": max_batch,
                   "block_size": block_size,
                   "num_blocks": 2 * max_blocks + 8,
                   "max_blocks_per_seq": max_blocks, "prefix_cache": True,
                   "prefill_token_budget": chunk, "kv_dtype": kv_dtype},
    }
    stats = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        server = serve_arch.build_server(config, seed, workdir)
        try:
            pools = server.engine.engine.cache.pools
            with _device_ops_traced("decoder",
                                    {tuple(p.shape) for p in pools}):
                err = serve_arch.check_probe(
                    server, config, {"table": [[prompt, 8]]}, seed,
                    tol=logits_tol, stats=stats)
            core = server.engine.target.core
            moe = core.moe_metrics()
        finally:
            server.close()
    log(f"[decoder] probe of {prompt} tokens past a window of {window}: "
        f"rel. L2 {err:.2e}, {stats.get('route_ties_taken')} of "
        f"{stats.get('route_rows')} routed rows tied; experts "
        f"{moe['mixed']['rows_routed_here']} rows routed here in "
        f"{moe['mixed']['layer_calls']} mixed layer calls")
    if moe["mixed"]["rows_routed_here"] == 0:
        raise AssertionError("no row was routed to the experts held here")
    if meter is not None:
        _phase_line("decoder", time.perf_counter() - t_phase, meter.take())
    return {"kernels": rows, "probe_rel_l2": err, "route": stats,
            "moe": moe}


def latent_phase(*, hidden=2048, heads=32, q_rank=1536, kv_rank=512,
                 nope=128, rope=64, v_dim=128, dense_width=7168,
                 experts=256, top_k=8, expert_width=768, vocab=16384,
                 prompt=8704, chunk=2048, block_size=16, max_batch=8,
                 weight_dtype="bfloat16", kv_dtype="bfloat16",
                 expect_kernel=True, logits_tol=DECODER_LOGITS_TOL,
                 tol=KERNEL_TOL, seed=0, meter=None) -> dict:
    """The ``joyai_llm_flash`` core at published widths: the latent
    (``v_dim``) ragged launch at ``heads`` query heads on ONE kv head of
    ``kv_rank + rope`` columns (stored in whole 128-lane tiles), a chunk and decode rows past 8 k positions,
    against its jnp reference, then a two-layer server (the dense layer,
    an expert layer with every expert) through ``build_server_from_spec``
    whose probe is held to the plain UN-absorbed reference's logits."""
    import jax.numpy as jnp
    import numpy as np
    import tempfile
    from benchmark.jobs import serve_latent
    pa = _kernel_module("paged_attention")

    t_phase = time.perf_counter()
    rng = np.random.default_rng(seed)
    width = -(-(kv_rank + rope) // 128) * 128        # as stored
    scale = (nope + rope) ** -0.5
    max_blocks = -(-(prompt + 64) // block_size)
    q_lens = (chunk,) + (1,) * max_batch
    kv_lens = [prompt] + list(rng.integers(2, prompt, size=max_batch - 1)) \
        + [prompt - 3]
    num_blocks = 1 + sum(-(-int(n) // block_size) for n in kv_lens)
    _, _, bt, kvl = _paged_inputs(
        rng, q_lens, kv_lens, heads=1, head_dim=1, block_size=block_size,
        num_blocks=num_blocks, max_blocks=max_blocks, pool_dtype="float32")
    q = jnp.asarray(rng.standard_normal((sum(q_lens), heads, width)),
                    kv_dtype)
    pool = jnp.asarray(rng.standard_normal(
        (num_blocks, 1, 1, block_size, width)), kv_dtype)
    rows = [_run_check(
        "paged_ragged/latent",
        f"R={sum(q_lens)} nh={heads} nkv=1 hd={width} v_dim={kv_rank}",
        lambda q_, p_, bt_, kl_: pa.paged_attention_ragged(
            q_, p_, bt_, q_lens, kl_, sm_scale=scale, v_dim=kv_rank),
        lambda q_, p_, bt_, kl_: pa.paged_attention_ragged_reference(
            q_, p_, bt_, q_lens, kl_, sm_scale=scale, v_dim=kv_rank),
        (q, pool, bt, kvl), expect_kernel, tol)]

    config = {
        "model_type": "joyai_llm_flash", "reference": "joyai_llm_flash",
        "hidden_size": hidden, "num_attention_heads": heads,
        "intermediate_size": dense_width,
        "moe_intermediate_size": expert_width, "q_lora_rank": q_rank,
        "kv_lora_rank": kv_rank, "qk_nope_head_dim": nope,
        "qk_rope_head_dim": rope, "v_head_dim": v_dim,
        "first_k_dense_replace": 1, "n_routed_experts": experts,
        "n_shared_experts": 1, "num_experts_per_tok": top_k,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "rope_interleave": True, "rope_theta": 32000000,
        "rms_norm_eps": 1e-6, "vocab_size": vocab,
        "weight_dtype": weight_dtype, "layers_run": [0, 1],
        "engine": {"mp": 1, "k": 0, "max_batch": max_batch,
                   "block_size": block_size,
                   "num_blocks": 2 * max_blocks + 8,
                   "max_blocks_per_seq": max_blocks, "prefix_cache": True,
                   "prefill_token_budget": chunk, "kv_dtype": kv_dtype},
    }
    stats = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        server = serve_latent.build_server(config, seed, workdir)
        try:
            cache = server.engine.engine.cache
            with _device_ops_traced("latent",
                                    {tuple(p.shape) for p in cache.pools}):
                probe = serve_latent.probe_engine(
                    server, config, {"table": [[prompt, 8]]}, seed)
            err = serve_latent.compare_probe(
                server.engine.target, config, probe, tol=logits_tol,
                stats=stats)
            per_token = cache.kv_bytes_per_token()
        finally:
            server.close()
    log(f"[latent] probe of {prompt} tokens: rel. L2 {err:.2e}, "
        f"{stats.get('route_ties_taken')} of {stats.get('route_rows')} "
        f"routed rows tied (widest gap {stats.get('route_widest_gap')}); "
        f"{per_token} cache bytes a token")
    if per_token != 2 * width * jnp.dtype(kv_dtype).itemsize:
        raise AssertionError(f"the latent pool holds {per_token} bytes a "
                             f"token over two layers of {width} columns")
    if meter is not None:
        _phase_line("latent", time.perf_counter() - t_phase, meter.take())
    return {"kernels": rows, "probe_rel_l2": err, "route": stats}


def conv_phase(*, hidden=2048, heads=32, kv_heads=8, dense_width=11776,
               experts=64, top_k=4, expert_width=1536, vocab=16384,
               prompt=2048, chunk=1024, block_size=16, max_batch=8,
               kernel=3, weight_dtype="bfloat16", kv_dtype="bfloat16",
               expect_kernel=True, logits_tol=DECODER_LOGITS_TOL,
               tol=KERNEL_TOL, seed=0, meter=None) -> dict:
    """The ``lfm2_moe`` core at published widths: the ragged launch over
    TWO heads of 64 a 128-lane pool row (``kv_heads / 2`` stored rows, a
    query head's operand zero in the other head's half) against the jnp
    reference over the same K/V kept a head of 64 each; the short
    convolution's mix (a chunk and decode rows through the state store,
    one XLA program) against a plain whole-sequence convolution; then a
    three-layer server (the dense conv layer, an attention and a conv
    layer with every expert) through ``build_server_from_spec`` whose
    probe, two chunks long, is held to the plain reference's logits."""
    import jax.numpy as jnp
    import numpy as np
    import tempfile
    from benchmark.jobs import serve_conv
    from paddle_tpu.inference import decoder, paged_cache
    pa = _kernel_module("paged_attention")

    t_phase = time.perf_counter()
    rng = np.random.default_rng(seed)
    hd = hidden // heads
    cfg = decoder.DecoderConfig.from_spec(dict(
        arch="lfm2_moe", hidden_size=hidden, num_attention_heads=heads,
        num_key_value_heads=kv_heads, layer_types=["full_attention"],
        num_dense_layers=1, intermediate_size=dense_width))
    pack = cfg.kv_pack
    max_blocks = -(-(prompt + 64) // block_size)
    q_lens = (chunk,) + (1,) * max_batch
    kv_lens = [prompt] + list(rng.integers(2, prompt, size=max_batch - 1)) \
        + [prompt - 3]
    num_blocks = 1 + sum(-(-int(n) // block_size) for n in kv_lens)
    _, _, bt, kvl = _paged_inputs(
        rng, q_lens, kv_lens, heads=1, head_dim=1, block_size=block_size,
        num_blocks=num_blocks, max_blocks=max_blocks, pool_dtype="float32")
    q = jnp.asarray(rng.standard_normal((sum(q_lens), heads, hd)), kv_dtype)
    pool = jnp.asarray(rng.standard_normal(
        (num_blocks, 2, kv_heads, block_size, hd)), kv_dtype)

    def packed(q_, p_, bt_, kl_):
        # the pool as the cache stores it, q as ``_attn_in`` hands it,
        # and each head's own half of the output as ``_attn_out`` takes it
        lane = decoder._lane_of_head(cfg)                # [heads, pack]
        rows = jnp.transpose(
            p_.reshape(num_blocks, 2, kv_heads // pack, pack, block_size,
                       hd), (0, 1, 2, 4, 3, 5)).reshape(
            num_blocks, 2, kv_heads // pack, block_size, pack * hd)
        wide = (q_[:, :, None, :] * lane[None, :, :, None]).astype(
            q_.dtype).reshape(-1, heads, pack * hd)
        out = pa.paged_attention_ragged(wide, rows, bt_, q_lens, kl_,
                                        sm_scale=cfg.attn_scale)
        return jnp.einsum("rhnd,hn->rhd", out.astype(jnp.float32).reshape(
            -1, heads, pack, hd), lane)
    rows = [_run_check(
        "paged_ragged/two_heads_a_row",
        f"R={sum(q_lens)} nh={heads} nkv={kv_heads} hd={hd} pack={pack}",
        packed,
        lambda q_, p_, bt_, kl_: pa.paged_attention_ragged_reference(
            q_, p_, bt_, q_lens, kl_),
        (q, pool, bt, kvl), expect_kernel, tol)]

    # the mix: slot 0's second chunk beside every slot's decode row
    # (slot 0 masked: it is mid-prefill), against the plain convolution
    cache = paged_cache.PagedKVCache(
        2, heads, pack * hd, block_size, max_batch * max_blocks + 8, max_batch,
        max_blocks_per_seq=max_blocks, dtype=kv_dtype,
        num_kv_heads=kv_heads // pack, sm_scale=cfg.attn_scale,
        layer_state=[None, (kernel - 1, hidden)])
    taps = rng.standard_normal((hidden, kernel)).astype(np.float32)
    seq = rng.standard_normal((max_batch, 2 * chunk + 1, hidden)) \
        .astype(np.float32)
    seq = np.asarray(jnp.asarray(seq, kv_dtype).astype(jnp.float32))

    def plain(u):
        y = np.zeros_like(u)
        for j in range(kernel):
            y[j:] += u[:len(u) - j] * taps[:, kernel - 1 - j]
        return y
    for slot in range(max_batch):
        cache.ensure(slot, chunk, write_from=0)
        cache.prefill_views(slot)[1].mix(
            jnp.asarray(seq[slot][None, :chunk], kv_dtype),
            jnp.asarray(taps))
    cache.ensure(0, 2 * chunk, write_from=chunk)
    for slot in range(1, max_batch):
        cache.ensure(slot, chunk + 1)
    mask = np.zeros(max_batch, bool)
    mask[0] = True
    cache.set_decode_mask(mask)
    view = cache.ragged_views([
        ("prefill", 0, chunk, chunk, 0),
        ("decode", np.full(max_batch, chunk), 1)])[1]
    u = np.concatenate([seq[0][chunk:2 * chunk], seq[:, chunk]])
    got = np.asarray(view.mix(jnp.asarray(u[None], kv_dtype),
                              jnp.asarray(taps)))[0]
    want = np.concatenate([plain(seq[0][:2 * chunk])[chunk:]] + [
        plain(seq[s][:chunk + 1])[-1:] for s in range(max_batch)])
    mix_err = float(np.abs(got[:chunk] - want[:chunk]).max()
                    / np.abs(want).max())
    mix_err = max(mix_err, float(np.abs(got[chunk + 1:] - want[chunk + 1:])
                                 .max() / np.abs(want).max()))
    log(f"[conv] mix of a {chunk}-row chunk beside {max_batch} decode rows "
        f"at d={hidden}: err={mix_err:.2e}")
    if mix_err > tol:
        raise AssertionError(f"conv mix: error {mix_err:.3e} > {tol}")
    del cache

    config = {
        "model_type": "lfm2_moe", "reference": "lfm2_moe",
        "hidden_size": hidden, "num_attention_heads": heads,
        "num_key_value_heads": kv_heads, "intermediate_size": dense_width,
        "moe_intermediate_size": expert_width, "num_dense_layers": 1,
        "num_experts": experts, "num_experts_per_tok": top_k,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "use_expert_bias": True, "conv_L_cache": kernel,
        "conv_bias": False, "norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "vocab_size": vocab, "weight_dtype": weight_dtype,
        "layer_types": ["conv", "conv", "full_attention", "conv"],
        "layers_run": [1, 2, 3],
        "engine": {"mp": 1, "k": 0, "max_batch": max_batch,
                   "block_size": block_size,
                   "num_blocks": 2 * max_blocks + 8,
                   "max_blocks_per_seq": max_blocks, "prefix_cache": False,
                   "prefill_token_budget": chunk, "kv_dtype": kv_dtype},
    }
    stats = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        server = serve_conv.build_server(config, seed, workdir)
        try:
            cache = server.engine.engine.cache
            with _device_ops_traced("conv",
                                    {tuple(p.shape) for p in cache.pools}):
                probe = serve_conv.probe_engine(
                    server, config, {"table": [[prompt, 8]]}, seed)
            err = serve_conv.compare_probe(
                server.engine.target, config, probe, tol=logits_tol,
                stats=stats)
            per_token, state = cache.kv_bytes_per_token(), cache.state_bytes()
        finally:
            server.close()
    log(f"[conv] probe of {prompt} tokens in chunks of {chunk}: rel. L2 "
        f"{err:.2e}, {stats.get('route_ties_taken')} of "
        f"{stats.get('route_rows')} routed rows tied (widest gap "
        f"{stats.get('route_widest_gap')}); {per_token} cache bytes a "
        f"token, {state} bytes of state store")
    size = jnp.dtype(kv_dtype).itemsize
    if per_token != 2 * kv_heads * hd * size or \
            state != 2 * max_batch * (kernel - 1) * hidden * size:
        raise AssertionError(
            f"one K/V layer holds {per_token} bytes a token and two conv "
            f"layers {state} bytes of state")
    if meter is not None:
        _phase_line("conv", time.perf_counter() - t_phase, meter.take())
    return {"kernels": rows, "mix_err": mix_err, "probe_rel_l2": err,
            "route": stats}


def main() -> None:
    t_start = time.perf_counter()
    device = require_tpu()
    import jax
    import paddle_tpu  # noqa: F401  (configures the compile cache)
    from paddle_tpu.framework.device import compile_cache_dir
    log(f"compile cache: {compile_cache_dir()} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    meter = CompileMeter()

    serving_phase(meter=meter)
    _free_device_memory("serving")
    kernel_phase(meter=meter)
    _free_device_memory("kernels")
    decoder_phase(meter=meter)
    _free_device_memory("decoder")
    latent_phase(meter=meter)
    _free_device_memory("latent")
    conv_phase(meter=meter)
    _free_device_memory("conv")
    trainer_phase(meter=meter)
    _free_device_memory("trainer")

    n = len(jax.devices())
    if n >= 4:
        trainer_four_chip_phase(meter=meter)
        _free_device_memory("trainer pp2 x mp2")
        serving_phase(mp=4, meter=meter)
    else:
        log(f"[four-chip] not run: {n} device(s)")

    log(f"chip_smoke passed in {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
