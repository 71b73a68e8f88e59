"""A fabricated run of the ``joyai-flash.long-decode`` cell for the readers
its new per-layer metrics use, with what each reads from it worked by hand.

``test_layer_metric_readers`` (``test_benchmark.py``) builds every run from
the GPT-3 configuration: it has no latent widths and no row lengths.
``plant`` adds them to such a run; ``tests/conftest.py`` applies it around
that test for the metrics listed in ``PLANTED_VALUES`` (the readers run for
real, on the planted run), and ``test_serve_latent.py`` holds the readers to
the hand-worked values. (``planted_afmoe.py`` does the same for the
``trinity-large.mixed-queue`` cell.)
"""
import json
import os

from benchmark import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
DECODE_SECONDS = 0.001        # device time of the planted decode launches
GMM_SECONDS = 0.03            # ... of the planted gmm launches

# one decode-only traced step with three rows, one mixed step
PLANTED_LENS = [100, 4096, 14000]
# latent_attn_roofline: pages of 16 rows of 640 stored columns in bfloat16,
# 20 480 B; rows at 100, 4 096 and 14 000 read 7 + 256 + 875 = 1 138 pages
# a layer: 23 306 240 B over 819 GB/s = 28.457 us; they cost 18 196 x 32
# heads x (576 + 512) x 2 = 1 267 023 872 FLOPs a layer over 197 TFLOP/s =
# 6.432 us: the bytes bound it. Five layers: 142.285 us, over 1 ms
_PAGES = 7 + 256 + 875
_BYTES_S = _PAGES * 16 * 640 * 2 / 819e9
_FLOPS_S = sum(PLANTED_LENS) * 32 * (576 + 512) * 2 / 197e12
# expert counters of the traced steps: a decode call of 64 rows over 4
# expert layers (512 assignments a layer over 220 experts), a mixed call of
# 2 112 (16 896 a layer, every expert)
MOE_TRACED = {
    "decode": {"calls": 1, "rows": 64, "layer_calls": 4,
               "rows_routed_here": 2048, "experts_hit": 880},
    "mixed": {"calls": 1, "rows": 2112, "layer_calls": 4,
              "rows_routed_here": 67584, "experts_hit": 1024},
}
# gmm_long_decode_roofline (gmm_roofline's reader): 1 904 (expert, call)
# pairs x 3 x 2048 x 768 x 2 B of weights, 69 632 rows x 2 x 2048 x 2 B in
# and out: 18 538 823 680 B over 819 GB/s = 22.636 ms; 69 632 x 6 x 2048 x
# 768 FLOPs over 197 TFLOP/s = 3.336 ms: the bytes bound it. Over 30 ms
_GMM_BYTES = 1904 * 3 * 2048 * 768 * 2 + 69632 * 2 * 2048 * 2
PLANTED_VALUES = {
    "latent_attn_roofline":
        100.0 * 5 * max(_BYTES_S, _FLOPS_S) / DECODE_SECONDS,
    "decode_ctx_tokens_mean": 18196.0,      # one decode-only step: the sum
    "gmm_long_decode_roofline": 100.0 * (_GMM_BYTES / 819e9) / GMM_SECONDS,
    "expert_rows_per_step.long-decode": 66.0,       # 67 584 / (4 x 256)
    "token_gap_ms_p95.long-decode": 454.0,  # of 30, 40, 500: 40 + 0.9 x 460
}


def config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash-1chip.json")) as f:
        return json.load(f)


def plant(run: dict) -> dict:
    """``run`` with the cell's configuration and what its readers read."""
    decode = metrics.Step(0.0, 1.0, 3, 0, 3, 700, 0)
    decode.decode_lens = list(PLANTED_LENS)
    mixed = metrics.Step(1.0, 2.0, 63, 2048, 64, 800, 0)
    mixed.decode_lens = [2000] * 63
    trace = dict(run["trace"], steps=2, op_seconds=dict(
        run["trace"]["op_seconds"],
        **{"mosaic:fwd_bf16_64_1_32_512_": DECODE_SECONDS,
           "mosaic:fwd_bf16_128_1_1024_512_": 0.05,
           "mosaic:gmm_bf16_4352_1536_": GMM_SECONDS / 3,
           "mosaic:gmm_bf16_49408_1536_": GMM_SECONDS / 3,
           "mosaic:gmm_bf16_49408_2048_": GMM_SECONDS / 3}))
    counters = dict(run["counters"], decode_ctx_tokens_mean=18196.0,
                    moe_traced=MOE_TRACED,
                    expert_rows_per_step=67584 / (4 * 256))
    series = dict(run["series"], token_gap_ms=[30.0, 40.0, 500.0])
    return dict(run, config=config(), steps=[decode, mixed], trace=trace,
                counters=counters, peaks=PEAKS, series=series)
