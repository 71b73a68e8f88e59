"""Serving job for a configuration with a LATENT cache (``arch``
``joyai_llm_flash``: multi-head latent attention, absorbed, over one row a
position): ``jobs/serve_arch.py``'s closed loop, probe, comparison and
counters as they are, with this job's own server spec (the published
DeepSeek-V3 keys), its own probe limit and the counters the latent
metrics read.

``serve_arch.run`` builds its server through its module's ``build_server``
and takes no other; this job runs it with that one name bound to its own
(``serve_arch.py`` is imported, not edited; PERF.md section 7 asks a
``benchmark`` PR for a ``build_server=`` argument there).
"""
from __future__ import annotations

import os
from unittest import mock

import numpy as np

from benchmark.jobs import serve_arch
from benchmark.jobs.serve_arch import (Loop, probe_engine,  # noqa: F401
                                       probed_positions)

# ||engine - ref|| / ||ref|| per probed position, as serve_arch's. The
# engine stores weights and latent pages in bfloat16, hands bfloat16
# between its products (the absorbed q and the attention's output over the
# latent included) and lets Mosaic's float32 dot run as one bfloat16 pass;
# the reference takes the same (rounded) weights, decompresses K and V per
# head and does everything in float32. The limit lies between two readings
# on the chip (PERF.md, Findings, PR 32; tools/probe_readings.py prints
# both): the engine gives 5.4e-3 to 6.2e-3 over its seeds and five
# positions; the reference itself, its matrices first rounded to 3 mantissa
# bits (a scaled float8 e4m3, the nearest precision below bfloat16's 7),
# gives 7.1e-2 to 4.0e-1 against itself unrounded and routes 5 673 of 16 400
# rows elsewhere outside the margin. 2e-2 is three times the first and under
# a third of the second. A wrong scale, RoPE pairing or a dropped norm read
# tens of percent at the tiny size (tests/test_mla_serving.py).
LOGITS_TOL = 2e-2
# keys of the configuration file that the server's spec takes as they are
MODEL_KEYS = ("hidden_size", "num_attention_heads", "intermediate_size",
              "moe_intermediate_size", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "first_k_dense_replace", "n_routed_experts",
              "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor", "rope_interleave", "rope_theta",
              "rms_norm_eps", "vocab_size", "weight_dtype")


def server_spec(config: dict, seed: int, workdir: str) -> dict:
    spec = dict(config["engine"])
    spec.update({k: config[k] for k in MODEL_KEYS})
    spec.update(
        arch=config["model_type"],
        num_hidden_layers=len(config["layers_run"]),
        model_seed=seed % 2**31, embed_seed=(seed + 1234) % 2**31,
        journal_path=os.path.join(workdir, "journal.wal"),
        snapshot_path=os.path.join(workdir, "snapshot.bin"))
    return spec


def build_server(config: dict, seed: int, workdir: str):
    # a program without the latent core (the parent of the PR that brought
    # it) would refuse the spec somewhere inside: fail here, at once
    try:
        from paddle_tpu.inference.decoder import ARCHS
    except ImportError:
        ARCHS = ()
    if config["model_type"] not in ARCHS:
        raise ImportError(f"this program's decoder core has no arch "
                          f"{config['model_type']!r}")
    from paddle_tpu.inference.router import build_server_from_spec
    return build_server_from_spec(server_spec(config, seed, workdir))


def compare_probe(tsm, config: dict, probe: dict, tol: float = LOGITS_TOL,
                  stats: dict | None = None, **reference_kw) -> float:
    """``serve_arch.compare_probe`` at this job's limit."""
    return serve_arch.compare_probe(tsm, config, probe, tol, stats,
                                    **reference_kw)


def decode_ctx_tokens_mean(steps) -> float | None:
    """Cache positions the decode rows of a decode-only step attend over
    (each row's prompt, what it has generated and the token it is fed),
    mean over the steps that are decode-only."""
    ctx = [sum(s.decode_lens) for s in steps
           if not s.prefill_tokens and getattr(s, "decode_lens", None)]
    return float(np.mean(ctx)) if ctx else None


def run(config: dict, traffic: dict, **kw) -> dict:
    """``serve_arch.run`` over this job's server, at this job's limit."""
    # the names serve_arch's loop reads of a configuration
    config = dict(config, num_experts=config["n_routed_experts"])
    with mock.patch.object(serve_arch, "build_server", build_server):
        out = serve_arch.run(config, traffic, logits_tol=LOGITS_TOL, **kw)
    ctx = decode_ctx_tokens_mean(out["steps"])
    if ctx is not None:
        out["counters"]["decode_ctx_tokens_mean"] = ctx
    return out
