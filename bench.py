"""Benchmark: Llama training throughput + MFU on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline (BASELINE.md): >=45% MFU target for Llama-class hybrid training
— vs_baseline = achieved_MFU / 0.45.

MFU accounting is the STRICT Megatron/PaLM convention: the vocab
projection is counted once (the logit head matmul); the input-embedding
gather, remat recompute, and the chunked-CE logit recompute are real
work that is NOT counted (see LlamaSpmdTrainer.flops_per_token).
Timing is windowed (sync at window boundaries only, never per step) and
the three window throughputs + std are reported alongside the mean.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


# bf16 peak FLOP/s per chip, keyed by substrings of ``device_kind``
# (Google Cloud TPU documentation). A device that is not in the table is
# an error, not a default.
_PEAK_BF16 = {
    "v5 lite": 197e12, "v5e": 197e12, "v5litepod": 197e12,
    "v5p": 459e12, "v4": 275e12, "v6e": 918e12, "v6 lite": 918e12,
}


def _peak_flops(device):
    if device.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU; jax found {device.platform!r} "
            f"({device.device_kind!r}) — a CPU run has no MFU to report")
    kind = device.device_kind.lower()
    for k, v in _PEAK_BF16.items():
        if k in kind:
            return v
    raise RuntimeError(
        f"no bf16 peak recorded for device_kind {device.device_kind!r}; "
        f"add it to _PEAK_BF16 with its source")


def main():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.llama_spmd import LlamaSpmdTrainer

    dev = jax.devices()[0]
    peak = _peak_flops(dev)     # raises off the chip, before any compile
    mesh_mod.build_mesh(dp=1, devices=[dev])

    # Llama-2-7B layer dims (hidden 4096, inter 11008, 32 heads,
    # TRUE 32000 vocab) with 2 layers so params + AdamW states fit
    # one chip's HBM; bf16 compute, bf16 moment storage (update math
    # fp32), chunked cross-entropy, seq 2048, tuned flash-attention
    # block sizes. MXU-saturating matmuls == honest 7B-class MFU;
    # flops_per_token scales with the layer count and counts the vocab
    # matmul ONCE.
    cfg = LlamaConfig(vocab_size=32000, hidden_size=4096,
                      intermediate_size=11008, num_hidden_layers=2,
                      num_attention_heads=32, num_key_value_heads=32,
                      max_position_embeddings=2048)
    batch, seq, steps, windows, warmup = 16, 2048, 5, 3, 2
    dtype = jnp.bfloat16
    moments = jnp.bfloat16

    # remat off: the 2-layer proxy + donated AdamW states leave room for
    # full activations at b16, so backward pays zero recompute. A config
    # that does not fit fails here; it is not retried under another policy.
    ids = np.random.randint(0, cfg.vocab_size, (batch, seq))
    trainer = LlamaSpmdTrainer(cfg, compute_dtype=dtype, remat=False,
                               remat_policy="full",
                               moments_dtype=moments, scan_unroll=2)
    float(trainer.train_step(ids))  # compile + first step (host sync)

    for _ in range(max(warmup - 1, 0)):
        float(trainer.train_step(ids))  # host sync
    jax.block_until_ready(trainer.params)
    win_tok_s = []
    toks = batch * seq * steps
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.train_step(ids)
        loss_v = float(loss)  # host transfer: hard sync of the chain
        jax.block_until_ready(trainer.params)
        win_tok_s.append(toks / (time.perf_counter() - t0))

    tok_s = float(np.mean(win_tok_s))
    # strict-convention flops/token (vocab matmul counted once; no
    # recompute, no embedding-gather flops)
    flops_tok = trainer.flops_per_token(seq)
    mfu = tok_s * flops_tok / peak

    try:
        from paddle_tpu.utils.op_coverage import coverage
        cov = coverage()
        op_cov = cov["reachable_pct"] if cov["total"] else None
        golden_cov = cov.get("golden_pct")
    except Exception:
        op_cov = golden_cov = None

    # step-time ablation: where the remaining non-MFU time lives
    # (fwd / fwd+bwd / backbone-only legs; optimizer = step - fwd_bwd,
    # head+CE = full - backbone). PT_BENCH_NO_ABLATE=1 skips.
    ablation = None
    import os
    if not os.environ.get("PT_BENCH_NO_ABLATE"):
        def _t(fn, n=3):
            fn()
            out = None
            t0 = time.perf_counter()
            for _ in range(n):
                out = fn()
            float(jnp.sum(jax.tree_util.tree_leaves(out)[0]
                          .astype(jnp.float32)))
            return round((time.perf_counter() - t0) / n * 1e3, 1)
        # half batch: the standalone value_and_grad holds grads + params
        # + optimizer states concurrently (no donation), which OOMs at
        # the headline batch — legs are labeled with their own batch
        ab_batch = max(1, batch // 2)
        jids = jnp.asarray(ids[:ab_batch])
        f_fwd = jax.jit(trainer.loss_fn)
        f_vg = jax.jit(jax.value_and_grad(trainer.loss_fn))

        def bb_loss(params, i, l):
            return trainer.forward_hidden(params, i).astype(
                jnp.float32).mean()
        f_bb = jax.jit(jax.value_and_grad(bb_loss))
        try:
            ablation = {
                "batch": ab_batch,
                "fwd_loss_ms": _t(lambda: f_fwd(trainer.params, jids,
                                                jids)),
                "fwd_bwd_ms": _t(lambda: f_vg(trainer.params, jids,
                                              jids)[0]),
                "fwd_bwd_backbone_ms": _t(
                    lambda: f_bb(trainer.params, jids, jids)[0]),
                "full_step_ms_headline_batch": round(
                    batch * seq / tok_s * 1e3, 1),
            }
        except Exception as e:
            ablation = {"error": f"{type(e).__name__}"}

    print(json.dumps({
        "metric": "llama_train_mfu_1chip",
        "value": round(mfu * 100, 2),
        "unit": "%MFU_strict_megatron_convention",
        "vs_baseline": round(mfu / 0.45, 4),
        "tokens_per_sec_per_chip": round(tok_s, 1),
        "tok_s_windows": [round(t, 1) for t in win_tok_s],
        "tok_s_std": round(float(np.std(win_tok_s)), 1),
        "flops_per_token_G": round(flops_tok / 1e9, 3),
        "params": trainer.param_count(),
        "op_coverage_reachable_pct": op_cov,
        "op_coverage_golden_pct": golden_cov,
        "ablation_ms": ablation,
        "device": str(dev),
    }))


if __name__ == "__main__":
    main()
