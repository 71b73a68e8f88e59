"""A fabricated run of the ``trinity-large.mixed-queue`` cell for the readers
that this cell's per-layer metrics use, with what each reads from it worked
by hand.

``test_layer_metric_readers`` (``test_benchmark.py``) hands every reader a
run "that has what it reads" and builds that run from the GPT-3
configuration: it has no expert counters, no row lengths and no grouped-GEMM
launch. ``plant`` adds them to such a run; ``tests/conftest.py`` applies it
around that test for the metrics listed in ``PLANTED_VALUES`` (the readers
run for real, on the planted run), and ``test_serve_arch.py`` holds the
readers to the hand-worked values.
"""
import json
import os

from benchmark import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
GMM_SECONDS = 0.004           # device time of the planted gmm launches
DECODE_SECONDS = 0.002        # ... of the planted decode-shaped launches

# one decode-only traced step with three rows, one mixed step
PLANTED_LENS = [100, 4096, 5000]
# expert counters of the traced steps: a decode call of 32 rows over 4
# expert layers, a mixed call of 2 080
MOE_TRACED = {
    "decode": {"calls": 1, "rows": 32, "layer_calls": 4,
               "rows_routed_here": 60, "experts_hit": 52},
    "mixed": {"calls": 1, "rows": 2080, "layer_calls": 4,
              "rows_routed_here": 4200, "experts_hit": 128},
}

# gmm_roofline: 180 (expert, call) pairs x 3 x 3072 x 3072 x 2 B of
# weights, 4 260 rows x 2 x 3072 x 2 B in and out: 10 244 505 600 B over
# 819 GB/s = 12.508 ms; 4 260 x 6 x 3072^2 FLOPs over 197 TFLOP/s = 1.224
# ms: the bytes bound it. Over 4 ms: 312.7 % (a planted time, far too short)
_GMM_BYTES = 180 * 3 * 3072 * 3072 * 2 + 4260 * 2 * 3072 * 2
# paged_attn_window_roofline: pages of 16 over layers s, s, s, s, f with a
# window of 4096: a row at 100 reads 5 x 7 = 35 pages, at 4096 5 x 256 =
# 1 280, at 5000 4 x 256 + 313 = 1 337: 2 652 pages of 2 x 8 x 16 x 128 x
# 2 B = 65 536 B over 819 GB/s = 0.21221 ms, over 2 ms
_PAGES = 35 + 1280 + 1337
PLANTED_VALUES = {
    "gmm_roofline": 100.0 * (_GMM_BYTES / 819e9) / GMM_SECONDS,
    "paged_attn_window_roofline":
        100.0 * (_PAGES * 65536 / 819e9) / DECODE_SECONDS,
    "expert_rows_per_step": 32.8125,            # 4 200 / (4 x 32)
    "window_pages_skipped_share": 25.0,
    "gmm_busy_share": None,                     # depends on the trace given
    "token_gap_ms_p95": 365.0,       # of 40, 50, 400: 50 + 0.9 x 350
}


def config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-large-ep8.json")) as f:
        return json.load(f)


def plant(run: dict) -> dict:
    """``run`` with the cell's configuration and what its readers read."""
    decode = metrics.Step(0.0, 1.0, 3, 0, 3, 700, 0)
    decode.decode_lens = list(PLANTED_LENS)
    mixed = metrics.Step(1.0, 2.0, 31, 2048, 32, 800, 0)
    mixed.decode_lens = [64] * 31
    trace = dict(run["trace"], steps=2, op_seconds=dict(
        run["trace"]["op_seconds"],
        **{"mosaic:gmm_bf16_608_6144_": GMM_SECONDS / 4,
           "mosaic:gmm_bf16_10368_6144_": GMM_SECONDS / 2,
           "mosaic:gmm_bf16_10368_3072_": GMM_SECONDS / 4,
           "mosaic:fwd_bf16_32_8_6_128_": DECODE_SECONDS,
           "mosaic:fwd_bf16_64_8_384_128_": 0.05}))
    counters = dict(run["counters"], moe_traced=MOE_TRACED,
                    expert_rows_per_step=4200 / (4 * 32),
                    window_pages_skipped_share=25.0)
    series = dict(run["series"], token_gap_ms=[40.0, 50.0, 400.0])
    return dict(run, config=config(), steps=[decode, mixed], trace=trace,
                counters=counters, peaks=PEAKS, series=series)
