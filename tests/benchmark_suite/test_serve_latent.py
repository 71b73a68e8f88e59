"""The ``joyai-flash.long-decode`` cell without the chip: its configuration
against the catalog's keys, its traffic table and recorded schedule, its job
end to end at toy widths, its probe and the control that must fail, and its
new readers on planted data worked by hand.
"""
import json
import os
import sys
import tempfile

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import cells, xplane  # noqa: E402
from benchmark.jobs import serve, serve_arch, serve_latent  # noqa: E402
from benchmark.layer_metrics import latent_attn_roofline  # noqa: E402
from tests.benchmark_suite import latent_play, planted_latent  # noqa: E402

CELL = latent_play.CELL
MANIFEST = cells.load_manifest(ROOT)
LOADED = cells.load_cell(CELL, MANIFEST, ROOT)
CONFIG, TRAFFIC = LOADED["config"], LOADED["traffic"]
FIXTURE = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb")
# the published config.json (the catalog's copy)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
# the last four: the readers of gmm_busy_share, gmm_roofline,
# expert_rows_per_step and token_gap_ms_p95 under names of this cell's own
# (test_serve_arch.py holds those four entries to mixed-queue alone)
NEW_METRICS = ("latent_attn_roofline", "mla_ms_per_step",
               "decode_ctx_tokens_mean", "mixed_step_ms_p50.long-decode",
               "submit_ms_per_request.long-decode",
               "gmm_busy_share.long-decode", "gmm_long_decode_roofline",
               "expert_rows_per_step.long-decode",
               "token_gap_ms_p95.long-decode")
PLAYED = 5


def tiny_job(**engine):
    """The cell at toy widths AND toy engine sizes: what a test can run."""
    config = latent_play.tiny(CONFIG, layers_run=[0, 1, 2])
    config["engine"] = dict(config["engine"], max_batch=4, block_size=4,
                            num_blocks=120, max_blocks_per_seq=16,
                            prefill_token_budget=16)
    config["engine"].update(engine)
    return config


# ---- the configuration ------------------------------------------------

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_keeps_every_published_key(key):
    reduced = CONFIG["reduced"]
    if key in reduced:
        assert reduced[key]["source"] == PUBLISHED[key]
        assert reduced[key]["here"] == CONFIG[key] != PUBLISHED[key]
        assert reduced[key]["why"]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_config_cut_is_the_leading_dense_layer_and_four_expert_layers():
    assert sorted(CONFIG["reduced"]) == ["num_hidden_layers"] == next(
        c["reduced"] for c in MANIFEST["configs"]
        if c["name"] == "joyai-llm-flash-1chip")
    assert CONFIG["layers_run"] == [0, 1, 2, 3, 4]
    assert len(CONFIG["layers_run"]) == CONFIG["num_hidden_layers"] == 5
    dense = [i for i in CONFIG["layers_run"]
             if i < CONFIG["first_k_dense_replace"]]
    assert dense == [0] and CONFIG["moe_layer_freq"] == 1
    assert "mtp_layer" in CONFIG["not_here"] and CONFIG["engine"]["k"] == 0
    with tempfile.TemporaryDirectory() as workdir:
        spec = serve_latent.server_spec(CONFIG, 2**31 + 9, workdir)
    assert spec["arch"] == "joyai_llm_flash" and spec["num_hidden_layers"] == 5
    assert spec["n_routed_experts"] == 256 and "experts_held" not in spec
    assert spec["num_experts_per_tok"] == 8 and spec["vocab_size"] == 129280
    assert spec["kv_lora_rank"] + spec["qk_rope_head_dim"] == 576
    assert 0 <= spec["model_seed"] < 2**31 and spec["max_batch"] == 64
    from paddle_tpu.inference.decoder import DecoderConfig
    cfg = DecoderConfig.from_spec(spec)
    assert (cfg.attention, cfg.residual, cfg.num_layers) == \
        ("mla", "pre_norm", 5)
    assert cfg.experts_held == cfg.num_experts == 256 and cfg.route_norm
    assert cfg.route_scale == 2.5 and cfg.num_dense_layers == 1
    assert cfg.attn_scale == 192 ** -0.5
    assert cfg.kv_width == CONFIG["latent_row"]["stored"] == 640
    assert CONFIG["latent_row"]["columns"] == 576


def test_config_memory_arithmetic():
    d, im = 2048, 768
    attn = d * 1536 + 1536 * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
    assert attn == 26_345_472
    moe = attn + 256 * 3 * d * im + 3 * d * im + d * 256
    dense = attn + 3 * d * 7168
    head = 129280 * d
    total = dense + 4 * moe + head
    assert round(moe / 1e6, 2) == 1239.55 and round(dense / 1e6, 2) == 70.39
    assert round(total / 1e6) == 5293                  # 10.59 GB in bf16
    engine = CONFIG["engine"]
    per_token = 5 * 640 * 2
    pool = engine["num_blocks"] * engine["block_size"] * per_token
    assert per_token == 6400 and round(pool / 1e9, 2) == 2.62
    assert round((2 * total + pool) / 1e9, 2) == 13.21
    most = sum(n * (p + 2048) for n, p in
               ((16, 1024), (16, 2048), (16, 4096), (12, 8192), (4, 12288)))
    assert most == 393216 < (engine["num_blocks"] - 1) * engine["block_size"]
    assert engine["max_blocks_per_seq"] * engine["block_size"] == 12288 + 2048


# ---- the manifest ------------------------------------------------------

def test_manifest_entries_of_the_cell():
    cell = LOADED["cell"]
    assert cell == {"name": CELL, "config": "joyai-llm-flash-1chip",
                    "traffic": "long-decode", "chips": 1,
                    "why": cell["why"]}
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "joyai-llm-flash-1chip")
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
        "config.json")
    assert all(len(x["why"]) <= 200 for x in (cell, entry))
    assert MANIFEST["workloads"][-1] is cell and MANIFEST["configs"][-1] is entry
    assert LOADED["end_to_end"] == ["serve_tok_per_s", "setup_s"]
    assert LOADED["job"] is serve_latent
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_are_the_cells_alone_and_last(metric):
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-len(NEW_METRICS):] == list(NEW_METRICS)
    entry = MANIFEST["per_layer"][names.index(metric)]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tok_per_s"
    assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       metric + ".json"))


def test_the_cell_reports_what_the_issue_lists():
    want = {"serve_tok_per_s", "rows_per_step_mean", "pool_peak_share",
            "decode_step_ms_p50", "xla_launches_per_step",
            "paged_attn_busy_share", "compiles_in_window.serve",
            "step_host_ms_p50", "dispatch_ms_per_step", "sched_ms_per_step",
            "page_grow_ms_per_step", "embed_sample_ms_per_step",
            "journal_ms_per_step", "moe_ms_per_step"}
    assert set(LOADED["per_layer"]) == (want - {"serve_tok_per_s"}) \
        | set(NEW_METRICS)
    # the K/V rooflines count K and V planes: not this cell's
    assert not {"paged_attn_roofline", "paged_attn_window_roofline",
                "window_pages_skipped_share"} & set(LOADED["per_layer"])
    # ... and four readers come under this cell's own names
    for old, new in (("gmm_busy_share", "gmm_busy_share.long-decode"),
                     ("gmm_roofline", "gmm_long_decode_roofline"),
                     ("expert_rows_per_step",
                      "expert_rows_per_step.long-decode"),
                     ("token_gap_ms_p95", "token_gap_ms_p95.long-decode")):
        specs = [json.load(open(os.path.join(
            ROOT, "benchmark", "layer_metrics", n + ".json")))
            for n in (old, new)]
        assert dict(specs[0], reader=specs[1]["reader"]) == specs[1]
        assert specs[1]["reader"] == specs[0].get("reader", old)


# ---- the traffic -------------------------------------------------------

def test_traffic_table_is_the_issues():
    table = TRAFFIC["table"]
    assert len(table) == 128 and TRAFFIC["clients"] == 64
    assert table[0][0] == 4096 and TRAFFIC["stagger_first_output"]
    prompts = sorted(p for p, _ in table[:64])
    assert prompts == sorted([1024] * 16 + [2048] * 16 + [4096] * 16
                             + [8192] * 12 + [12288] * 4)
    assert sum(prompts) / 64 == 4096
    cycle = [1024, 1536, 2048]
    for i in range(64):                  # a client keeps its prompt length
        assert table[i][0] == table[i + 64][0]
        assert cycle.index(table[i + 64][1]) == \
            (cycle.index(table[i][1]) + 1) % 3
    assert serve.client_schedule(TRAFFIC, 7, 1) == tuple(table[71])
    assert serve.client_schedule(TRAFFIC, 7, 0) == (12288, 192)   # staggered


@pytest.fixture(scope="module")
def played():
    """PLAYED steps of the table on the toy model for two seeds."""
    return [latent_play.play(latent_play.tiny(CONFIG), TRAFFIC, seed, PLAYED)
            for seed in (11, 3_000_000_019)]


def test_schedule_is_the_same_for_every_seed(played):
    (a, shapes_a, faults_a), (b, shapes_b, faults_b) = played
    rows = [latent_play.rows_of(loop) for loop in (a, b)]
    assert rows[0] == rows[1] and shapes_a == shapes_b
    assert not faults_a and not faults_b
    ids_a, ids_b = (list(loop.prompts.values()) for loop in (a, b))
    assert all(x != y for x, y in zip(ids_a, ids_b))
    assert len({tuple(p[:8]) for p in ids_a}) == len(ids_a) == 64
    assert all(len(s.decode_lens) == s.decode_rows for s in a.steps)
    # ... and the recorded schedule begins with what was just played
    with open(latent_play.SCHEDULE) as f:
        recorded = json.load(f)
    assert recorded["steps"][:PLAYED] == rows[0]
    assert recorded["shapes"][:PLAYED] == shapes_a


def test_no_step_shape_first_appears_after_warmup():
    """On the schedule recorded once on the CPU (a long play of the same
    loop; ``test_schedule_is_the_same_for_every_seed`` holds its start to
    what the code plays today)."""
    with open(latent_play.SCHEDULE) as f:
        recorded = json.load(f)
    warm = TRAFFIC["warmup_steps"]
    steps = recorded["steps"]
    shapes = [json.dumps([shape, step[0] > 0])
              for shape, step in zip(recorded["shapes"], steps)]
    assert 128 < warm < len(shapes) - 1500    # past the first fill; room
    assert set(shapes[warm:]) <= set(shapes[:warm])
    # nothing is preempted at the configured pool; it fills past 70 %
    blocks = CONFIG["engine"]["num_blocks"]
    assert 0.70 * blocks < max(s[3] for s in steps) < blocks - 1
    # about one step in twelve carries prompt chunks
    mixed = sum(1 for s in steps[warm:] if s[1]) / len(steps[warm:])
    assert 0.05 < mixed < 0.12
    assert {s[1] for s in steps if s[1]} <= {1024, 2048}
    assert max(s[0] for s in steps) == 64


# ---- the job, end to end on a tiny cell --------------------------------

def test_a_tiny_cell_runs_and_reports_its_counters():
    traffic = {"clients": 3, "warmup_steps": 4, "why": "a test",
               "table": [[24, 13], [16, 9], [8, 14], [24, 8], [8, 16],
                         [16, 11]]}
    out = serve_latent.run(tiny_job(), traffic, seed=3, seconds=0.5,
                           log=lambda m: None)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["e2e"]["serve_tok_per_s"] > 0
    assert "ttft_p50_ms" not in out["e2e"]
    c = out["counters"]
    assert c["probe_rel_l2"] < 1e-5 and c["steps"] == len(out["steps"])
    moe = c["moe_window"]
    assert moe["mixed"]["rows_routed_here"] == 2 * 2 * moe["mixed"]["rows"]
    assert c["expert_rows_per_step"] == pytest.approx(
        2 * moe["mixed"]["rows"] / (moe["mixed"]["calls"] * 8))
    lens = [sum(s.decode_lens) for s in out["steps"]
            if not s.prefill_tokens and s.decode_lens]
    assert lens and c["decode_ctx_tokens_mean"] == pytest.approx(
        np.mean(lens))
    assert "moe_traced" not in c                # an untraced run
    assert serve_latent.decode_ctx_tokens_mean([]) is None


def test_the_lower_precision_control_reads_not_correct():
    """``tools/probe_readings.py`` takes this job too: the engine passes
    the comparison that decides ``correct``; the reference with its
    matrices rounded to 3 mantissa bits does not."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import probe_readings
    got = probe_readings.readings(tiny_job(max_batch=2), {"table": [[24, 4]]},
                                  serve_latent, 5)
    assert got["limit"] == serve_latent.LOGITS_TOL
    assert got["engine"]["correct"] and max(got["engine"]["rel_l2"]) < 1e-4
    assert not got["rounded_reference"]["correct"]
    assert min(got["rounded_reference"]["rel_l2"]) > got["limit"]


def test_probe_fails_on_wrong_logits(monkeypatch):
    from benchmark.reference import joyai_llm_flash
    real = joyai_llm_flash.logits
    monkeypatch.setattr(joyai_llm_flash, "logits",
                        lambda *a, **k: np.roll(real(*a, **k), 1, axis=0))
    config = tiny_job(max_batch=2)
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_latent.build_server(config, 5, workdir)
        try:
            probe = serve_latent.probe_engine(server, config,
                                              {"table": [[24, 4]]}, 5)
            with pytest.raises(AssertionError, match="reference"):
                serve_latent.compare_probe(server.engine.target, config,
                                           probe, tol=1e-4)
        finally:
            server.close()


def test_a_program_without_the_latent_core_fails_at_once(monkeypatch):
    """The parent under this PR's benchmark files: an ImportError before
    any weight is drawn."""
    from paddle_tpu.inference import decoder
    monkeypatch.setattr(decoder, "ARCHS", {"afmoe": ("gqa", "sandwich")})
    with pytest.raises(ImportError, match="joyai_llm_flash"):
        serve_latent.build_server(CONFIG, 1, "/nonexistent")
    monkeypatch.delattr(decoder, "ARCHS")
    with pytest.raises(ImportError, match="joyai_llm_flash"):
        serve_latent.build_server(CONFIG, 1, "/nonexistent")


def test_the_job_runs_serve_archs_loop_over_its_own_server(monkeypatch):
    """``run`` binds ``serve_arch.build_server`` for the call and hands it
    back."""
    seen = {}

    def fake_run(config, traffic, **kw):
        seen.update(build=serve_arch.build_server, kw=kw,
                    experts=config["num_experts"])
        return {"steps": [], "counters": {}}
    monkeypatch.setattr(serve_arch, "run", fake_run)
    before = serve_arch.build_server
    out = serve_latent.run(CONFIG, TRAFFIC, seed=1, seconds=1.0)
    assert seen["build"] is serve_latent.build_server
    assert serve_arch.build_server is before
    assert seen["kw"]["logits_tol"] == serve_latent.LOGITS_TOL
    assert seen["experts"] == 256 and "num_experts" not in CONFIG
    assert "decode_ctx_tokens_mean" not in out["counters"]


# ---- the readers, on planted data worked by hand ------------------------

@pytest.fixture(scope="module")
def planted():
    trace = xplane.summarize(FIXTURE)
    return planted_latent.plant({"trace": trace, "counters": {},
                                 "series": {}, "e2e": {}})


@pytest.mark.parametrize("metric", sorted(planted_latent.PLANTED_VALUES))
def test_cell_readers_on_planted_data(planted, metric):
    got = cells.read_layer_metric(metric, planted)
    assert got["value"] == pytest.approx(
        planted_latent.PLANTED_VALUES[metric], rel=1e-9)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert got["unit"] == entry["unit"]
    empty = dict(planted, trace=None, counters={}, steps=[], series={})
    assert cells.read_layer_metric(metric, empty) is None


def test_latent_roofline_by_hand():
    config = planted_latent.config()
    assert latent_attn_roofline.row_width(config) == 576
    assert latent_attn_roofline.page_bytes(config) == 16 * 640 * 2 == 20480
    assert latent_attn_roofline.row_flops(config, 1000) == \
        1000 * 32 * (576 + 512) * 2
    least = latent_attn_roofline.least_seconds(
        config, planted_latent.PEAKS, planted_latent.PLANTED_LENS)
    assert least == pytest.approx(5 * 1138 * 20480 / 819e9)       # bytes
    assert planted_latent.PLANTED_VALUES["latent_attn_roofline"] == \
        pytest.approx(14.23, abs=0.005)
    assert planted_latent.PLANTED_VALUES["gmm_long_decode_roofline"] == \
        pytest.approx(75.45, abs=0.005)
    # the launch pattern is the decode-shaped latent launch's alone
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", "latent_attn_roofline.json")))
    import re
    pat = re.compile(spec["args"]["pattern"])
    assert pat.match("mosaic:fwd_bf16_64_1_32_512_")
    assert not pat.match("mosaic:fwd_bf16_128_1_1024_512_")       # mixed
    assert not pat.match("mosaic:fwd_bf16_32_8_6_128_")          # trinity's
    # a program that has no latent widths gives the reader nothing
    gpt3 = dict(planted_latent.plant({"trace": {"op_seconds": {}},
                                      "counters": {}, "series": {}}),
                config={"x": 1})
    assert latent_attn_roofline.read(gpt3, spec["args"]["pattern"]) is None


def test_mla_span_metric_reads_the_fabricated_session(session):
    """``mla_ms_per_step``: the ``mla`` spans of a round, mean over the
    whole rounds (the fabricated session's two, with 3 + 2 and 4 ms)."""
    for ts, dur, rnd in ((0.015, 0.003, 1), (0.020, 0.002, 1),
                         (0.206, 0.004, 2), (0.302, 0.009, 3)):
        session.events.append({"name": "mla", "ph": "X", "ts": ts,
                               "dur": dur, "args": {"round": rnd,
                                                    "parent": "model"}})
    got = cells.read_layer_metric("mla_ms_per_step",
                                  {"trace": {"steps": 2}})
    assert got == {"value": pytest.approx(4.5), "unit": "ms"}
    assert cells.read_layer_metric("mla_ms_per_step", {"trace": None}) is None
