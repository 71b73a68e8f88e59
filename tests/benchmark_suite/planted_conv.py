"""A fabricated run of the ``lfm2-24b.busy-chat`` cell for the readers its
new per-layer metrics use, with what each reads from it worked by hand.

``test_layer_metric_readers`` (``test_benchmark.py``) builds every run from
the GPT-3 configuration: it has no layer kinds, no stored K/V row and no row
lengths. ``plant`` adds them to such a run; ``tests/conftest.py`` applies it
around that test for the metrics listed in ``PLANTED_VALUES`` (the readers
run for real, on the planted run), and ``test_serve_conv.py`` holds the
readers to the hand-worked values. (``planted_afmoe.py`` and
``planted_latent.py`` do the same for their cells.)
"""
import json
import os

from benchmark import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
DECODE_SECONDS = 0.0005       # device time of the planted decode launches
GMM_SECONDS = 0.03            # ... of the planted gmm launches

# one decode-only traced step with three rows, one mixed step
PLANTED_LENS = [100, 1300, 4864]
# kv_layers_attn_roofline: a page of a K/V layer is 2 planes x 4 stored rows
# of 128 columns x 16 positions in bfloat16, 32 768 B; rows at 100, 1 300 and
# 4 864 read 7 + 82 + 304 = 393 pages a layer on the TWO layers of the nine
# that hold K/V: 25 755 648 B over 819 GB/s = 31.448 us, over 0.5 ms
_PAGES = 7 + 82 + 304
_KV_BYTES = 2 * _PAGES * 32768
# expert counters of the traced steps: a decode call of 192 rows over 8
# expert layers (768 assignments a layer, every one of 64 experts), a mixed
# call of 1 216 (4 864 a layer)
MOE_TRACED = {
    "decode": {"calls": 1, "rows": 192, "layer_calls": 8,
               "rows_routed_here": 6144, "experts_hit": 512},
    "mixed": {"calls": 1, "rows": 1216, "layer_calls": 8,
              "rows_routed_here": 38912, "experts_hit": 512},
}
# gmm_busy_chat_roofline (gmm_roofline's reader): 1 024 (expert, call) pairs
# x 3 x 2048 x 1536 x 2 B of weights, 45 056 rows x 2 x 2048 x 2 B in and
# out: 19 696 451 584 B over 819 GB/s = 24.049 ms; 45 056 x 6 x 2048 x 1536
# FLOPs over 197 TFLOP/s = 4.317 ms: the bytes bound it. Over 30 ms
_GMM_BYTES = 1024 * 3 * 2048 * 1536 * 2 + 45056 * 2 * 2048 * 2
PLANTED_VALUES = {
    "kv_layers_attn_roofline": 100.0 * (_KV_BYTES / 819e9) / DECODE_SECONDS,
    "prefill_segments_carried_share": 37.5,
    "gmm_busy_chat_roofline": 100.0 * (_GMM_BYTES / 819e9) / GMM_SECONDS,
    "expert_rows_per_step.busy-chat": 76.0,         # 38 912 / (8 x 64)
    "token_gap_ms_p95.busy-chat": 454.0,    # of 30, 40, 500: 40 + 0.9 x 460
}


def config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b-1chip.json")) as f:
        return json.load(f)


def plant(run: dict) -> dict:
    """``run`` with the cell's configuration and what its readers read."""
    decode = metrics.Step(0.0, 1.0, 3, 0, 3, 700, 0)
    decode.decode_lens = list(PLANTED_LENS)
    mixed = metrics.Step(1.0, 2.0, 191, 1024, 192, 800, 0)
    mixed.decode_lens = [2000] * 191
    trace = dict(run["trace"], steps=2, op_seconds=dict(
        run["trace"]["op_seconds"],
        **{"mosaic:fwd_bf16_192_4_8_128_": DECODE_SECONDS,
           "mosaic:fwd_bf16_208_4_512_128_": 0.05,
           "mosaic:gmm_bf16_1536_3072_": GMM_SECONDS / 3,
           "mosaic:gmm_bf16_9728_3072_": GMM_SECONDS / 3,
           "mosaic:gmm_bf16_9728_2048_": GMM_SECONDS / 3}))
    counters = dict(run["counters"], moe_traced=MOE_TRACED,
                    prefill_segments_carried_share=37.5,
                    expert_rows_per_step=38912 / (8 * 64))
    series = dict(run["series"], token_gap_ms=[30.0, 40.0, 500.0])
    return dict(run, config=config(), steps=[decode, mixed], trace=trace,
                counters=counters, peaks=PEAKS, series=series)
