"""Serving job for a configuration whose layers have a KIND (``arch``
``lfm2_moe``: gated short-convolution layers, which hold no K/V and keep two
rows a slot in the cache's state store, beside attention layers over paged
K/V): ``jobs/serve_arch.py``'s closed loop, probe, comparison and counters
as they are, with this job's own server spec (the published LFM2 keys), its
own probe limit and the counter the state store's metric reads.

The probe is ``serve_arch``'s: one request alone at the traffic table's
first prompt length. The cell's is two prefill chunks long, so the last
prompt row and the four decode rows that are compared with the reference's
whole-sequence convolution lie ACROSS a carry of the state between two
chunks, and across four more between decode steps.

``serve_arch.run`` builds its server through its module's ``build_server``
and takes no other; this job runs it with that one name bound to its own,
as ``jobs/serve_latent.py`` does (``serve_arch.py`` is imported, not
edited; PERF.md section 7 asks a ``benchmark`` PR for the argument).
"""
from __future__ import annotations

import os
from unittest import mock

from benchmark.jobs import serve_arch
from benchmark.jobs.serve_arch import (Loop, probe_engine,  # noqa: F401
                                       probed_positions)

# ||engine - ref|| / ||ref|| per probed position, as serve_arch's. The
# engine stores weights, K/V pages and the state store's rows in bfloat16,
# hands bfloat16 between its products (u = B * x and C included) and lets
# Mosaic's float32 dot run as one bfloat16 pass; the reference takes the same
# (rounded) weights and does everything in float32, the convolution over the
# whole sequence. The limit lies between two readings on the chip (PERF.md,
# Findings, PR 35; tools/probe_readings.py prints both): the engine gives
# 1.07e-2 to 1.34e-2 over its seeds and five positions (nine layers: the
# other two archs' cuts have five and read 6e-3; three layers of this one
# read 5.7e-3 in chip_smoke.py); the reference itself, its matrices first
# rounded to 3 mantissa bits (a scaled float8 e4m3, the nearest precision
# below bfloat16's 7), gives 1.7e-1 to 2.7e-1 against itself unrounded. 4e-2
# is three times the first and under a quarter of the second. A lost state
# carry, reversed taps, a swapped gate, skipped RoPE or a dropped QK-norm
# read tens of percent at the tiny size (tests/test_conv_state_serving.py).
LOGITS_TOL = 4e-2
# keys of the configuration file that the server's spec takes as they are
MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "moe_intermediate_size",
              "num_dense_layers", "num_experts", "num_experts_per_tok",
              "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
              "conv_L_cache", "conv_bias", "norm_eps", "rope_parameters",
              "vocab_size", "weight_dtype")


def server_spec(config: dict, seed: int, workdir: str) -> dict:
    spec = dict(config["engine"])
    spec.update({k: config[k] for k in MODEL_KEYS})
    spec.update(
        arch=config["model_type"],
        layer_types=serve_arch.layer_types(config),
        model_seed=seed % 2**31, embed_seed=(seed + 1234) % 2**31,
        journal_path=os.path.join(workdir, "journal.wal"),
        snapshot_path=os.path.join(workdir, "snapshot.bin"))
    return spec


def build_server(config: dict, seed: int, workdir: str):
    # a program without the arch (the parent of the PR that brought it)
    # would refuse the spec's layer types somewhere inside: fail here, at
    # once, with an ImportError
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


def carried_share(collector) -> float | None:
    """Of the prompt segments the state store saw in a profile session
    (``slot_state`` gauge, one sample a model call), the share, in percent,
    that continued a prompt from stored rows: the later chunks. None where
    the session saw no prompt segment, or the program has no such gauge."""
    total = carried = 0
    for ev in (collector.events if collector is not None else ()):
        args = ev.get("args") or {}
        if ev.get("ph") == "C" and ev.get("name") == "slot_state":
            total += args.get("prompt_segments", 0)
            carried += args.get("prompt_segments_carried", 0)
    return 100.0 * carried / total if total else None


def run(config: dict, traffic: dict, **kw) -> dict:
    """``serve_arch.run`` over this job's server, at this job's limit."""
    with mock.patch.object(serve_arch, "build_server", build_server):
        out = serve_arch.run(config, traffic, logits_tol=LOGITS_TOL, **kw)
    if out["counters"].get("moe_traced") is not None:    # a traced run
        from paddle_tpu.inference import telemetry
        share = carried_share(telemetry.last_session_collector())
        if share is not None:
            out["counters"]["prefill_segments_carried_share"] = share
    return out
