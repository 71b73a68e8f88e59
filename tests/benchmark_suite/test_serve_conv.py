"""The ``lfm2-24b.busy-chat`` cell without the chip: its configuration
against the catalog's keys and its own arithmetic, its traffic table and
recorded schedule, its job end to end at toy widths, its probe and the
control that must fail, and its new readers on planted data worked by hand.

These tests find their entries BY NAME: none holds an entry to be the
manifest's last, or a shared list to this cell alone, so that the next
configuration breaks nothing here.
"""
import json
import os
import re
import sys
import tempfile

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import cells, xplane  # noqa: E402
from benchmark.jobs import serve, serve_arch, serve_conv  # noqa: E402
from benchmark.layer_metrics import kv_layers_attn_roofline  # noqa: E402
from tests.benchmark_suite import conv_play, planted_conv  # noqa: E402

CELL = conv_play.CELL
MANIFEST = cells.load_manifest(ROOT)
LOADED = cells.load_cell(CELL, MANIFEST, ROOT)
CONFIG, TRAFFIC = LOADED["config"], LOADED["traffic"]
FIXTURE = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb")
PERIOD = ["conv", "conv", "full_attention", "conv"]
# the published config.json (the catalog's copy)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PERIOD * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}
# the last six: readers that are there, under names of this cell's own
NEW_METRICS = ("conv_ms_per_step", "prefill_segments_carried_share",
               "kv_layers_attn_roofline", "gmm_busy_share.busy-chat",
               "gmm_busy_chat_roofline", "expert_rows_per_step.busy-chat",
               "token_gap_ms_p95.busy-chat", "mixed_step_ms_p50.busy-chat",
               "submit_ms_per_request.busy-chat")
SHARED = ("rows_per_step_mean", "pool_peak_share", "decode_step_ms_p50",
          "xla_launches_per_step", "paged_attn_busy_share",
          "compiles_in_window.serve", "step_host_ms_p50",
          "dispatch_ms_per_step", "sched_ms_per_step",
          "page_grow_ms_per_step", "embed_sample_ms_per_step",
          "journal_ms_per_step", "moe_ms_per_step")
PLAYED = 5


def tiny_job(**engine):
    """The cell at toy widths AND toy engine sizes: what a test can run."""
    config = conv_play.tiny(CONFIG)
    config["engine"] = dict(config["engine"], max_batch=4, block_size=4,
                            num_blocks=120, max_blocks_per_seq=16,
                            prefill_token_budget=16)
    config["engine"].update(engine)
    return config


def _entry(kind: str, name: str) -> dict:
    return next(e for e in MANIFEST[kind] if e["name"] == name)


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


def test_config_is_the_catalogs_entry():
    """Where the guides are installed: every key of the catalog's copy."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "LFM2-24B-A2B")
    assert entry["config"] == PUBLISHED
    assert entry["source_url"] == CONFIG["source"]


def test_config_cut_is_one_dense_conv_layer_and_two_periods():
    assert sorted(CONFIG["reduced"]) == ["num_dense_layers",
                                         "num_hidden_layers"] \
        == sorted(_entry("configs", "lfm2-24b-a2b-1chip")["reduced"])
    assert CONFIG["layers_run"] == list(range(1, 10))
    assert len(CONFIG["layers_run"]) == CONFIG["num_hidden_layers"] == 9
    kinds = serve_arch.layer_types(CONFIG)
    assert kinds == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    assert kinds.count("full_attention") * 3 == kinds[1:].count("conv")
    # the stage's one dense layer is published layer 1
    assert [i for i in CONFIG["layers_run"]
            if i < PUBLISHED["num_dense_layers"]] == [1]
    assert CONFIG["num_dense_layers"] == 1 and CONFIG["engine"]["k"] == 0
    assert "state_snapshots" in CONFIG["not_here"]
    assert not CONFIG["engine"]["prefix_cache"]
    with tempfile.TemporaryDirectory() as workdir:
        spec = serve_conv.server_spec(CONFIG, 2**31 + 9, workdir)
    assert spec["arch"] == "lfm2_moe" and len(spec["layer_types"]) == 9
    assert spec["num_experts"] == 64 and "experts_held" not in spec
    assert spec["num_experts_per_tok"] == 4 and spec["vocab_size"] == 65536
    assert "head_dim" not in spec and "rope_theta" not in spec
    assert 0 <= spec["model_seed"] < 2**31 and spec["max_batch"] == 192
    from paddle_tpu.inference.decoder import DecoderConfig
    cfg = DecoderConfig.from_spec(spec)
    assert (cfg.attention, cfg.residual, cfg.num_layers) == \
        ("gqa", "pre_norm", 9)
    assert cfg.experts_held == cfg.num_experts == 64 and cfg.route_norm
    assert cfg.head_dim == 64 and cfg.attn_scale == 0.125
    row = CONFIG["kv_row"]
    assert (cfg.kv_pack, cfg.num_key_value_heads // cfg.kv_pack,
            cfg.kv_width) == (row["heads_a_row"], row["stored_heads"],
                              row["stored_width"]) == (2, 4, 128)


def test_config_memory_arithmetic():
    d, im, dense_w = 2048, 1536, 11776
    conv = d * 3 * d + d * d + d * 3
    attn = d * 32 * 64 + 2 * d * 8 * 64 + 32 * 64 * d
    assert (conv, attn) == (16_783_360, 10_485_760)
    experts = 64 * 3 * d * im + d * 64
    assert round(experts / 1e6, 2) == 604.11
    dense = 3 * d * dense_w
    head = 65536 * d
    total = conv + dense + 8 * experts + 2 * attn + 6 * conv + head
    assert round((conv + dense) / 1e6, 2) == 89.14
    assert round(total / 1e6) == 5178                  # 10.36 GB in bf16
    engine = CONFIG["engine"]
    row = CONFIG["kv_row"]
    per_token = 2 * 2 * row["stored_heads"] * row["stored_width"] * 2
    assert per_token == 4096 == 2 * 2 * 8 * 64 * 2     # nothing padded
    assert 9 * 2 * 8 * 64 * 2 == 18432       # K/V in every layer instead
    state = 7 * 2 * d * 2
    assert state == 57344 and round(192 * state / 1e6, 1) == 11.0
    pool = engine["num_blocks"] * engine["block_size"] * per_token
    assert round(pool / 1e9, 2) == 1.57
    assert round((2 * total + pool + 192 * state) / 1e9, 2) == 11.94
    most = 12 * sum(n * (p + 768) for n, p in
                    ((8, 512), (4, 1024), (3, 2048), (1, 4096)))
    assert most == 368640 < (engine["num_blocks"] - 1) * engine["block_size"]
    assert engine["max_blocks_per_seq"] * engine["block_size"] == 4096 + 768
    # what the engine builds at these sizes, without a weight drawn
    from paddle_tpu.inference import paged_cache
    kinds = serve_arch.layer_types(CONFIG)
    cache = paged_cache.PagedKVCache(
        9, 32, 128, 16, 8, 2, dtype="bfloat16", num_kv_heads=4,
        sm_scale=0.125,
        layer_state=[(2, d) if k == "conv" else None for k in kinds])
    assert cache.kv_bytes_per_token() == per_token
    assert cache.state_bytes() == 2 * state


# ---- the manifest ------------------------------------------------------

def test_manifest_entries_of_the_cell():
    cell = LOADED["cell"]
    assert cell == {"name": CELL, "config": "lfm2-24b-a2b-1chip",
                    "traffic": "busy-chat", "chips": 1, "why": cell["why"]}
    entry = _entry("configs", "lfm2-24b-a2b-1chip")
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/lfm2-24b-a2b-1chip.json"
    assert all(0 < len(x["why"]) <= 200 for x in (cell, entry))
    assert "9 layers" in cell["why"] and "stage" in cell["why"]
    assert LOADED["end_to_end"] == ["serve_tok_per_s", "setup_s"]
    assert LOADED["job"] is serve_conv
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_are_in_the_manifest_with_their_files(metric):
    entry = _entry("per_layer", metric)
    assert CELL in entry["workloads"]
    assert entry["moves"] == "serve_tok_per_s"
    assert sorted(entry) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["unit"] == entry["unit"]
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        spec.get("reader", metric).replace("-", "_") + ".py"))


def test_the_cell_reports_what_the_issue_lists():
    assert set(LOADED["per_layer"]) == set(SHARED) | set(NEW_METRICS)
    assert CELL in _entry("end_to_end", "serve_tok_per_s")["workloads"]
    # the other cells' K/V rooflines count every layer: not this cell's
    assert not {"paged_attn_roofline", "paged_attn_window_roofline",
                "latent_attn_roofline", "window_pages_skipped_share",
                "mla_ms_per_step"} & set(LOADED["per_layer"])
    # ... and six readers come under this cell's own names
    for old, new in (("gmm_busy_share", "gmm_busy_share.busy-chat"),
                     ("gmm_roofline", "gmm_busy_chat_roofline"),
                     ("expert_rows_per_step",
                      "expert_rows_per_step.busy-chat"),
                     ("token_gap_ms_p95", "token_gap_ms_p95.busy-chat"),
                     ("mixed_step_ms_p50.long-decode",
                      "mixed_step_ms_p50.busy-chat"),
                     ("submit_ms_per_request.long-decode",
                      "submit_ms_per_request.busy-chat")):
        specs = [json.load(open(os.path.join(
            ROOT, "benchmark", "layer_metrics", n + ".json")))
            for n in (old, new)]
        assert dict(specs[0], reader=specs[1]["reader"]) == specs[1]
        assert specs[1]["reader"] == specs[0].get("reader", old)


# ---- the traffic -------------------------------------------------------

def test_traffic_table_is_the_issues():
    table = TRAFFIC["table"]
    assert len(table) == 576 and TRAFFIC["clients"] == 192
    assert table[0][0] == 2048 and TRAFFIC["stagger_first_output"]
    assert table[0][0] == 2 * CONFIG["engine"]["prefill_token_budget"]
    prompts = [p for p, _ in table[:192]]
    assert prompts == prompts[:16] * 12
    assert sorted(prompts[:16]) == [512] * 8 + [1024] * 4 + [2048] * 3 \
        + [4096]
    assert sum(prompts) / 192 == 1152
    assert not any(a >= 2048 and b >= 2048
                   for a, b in zip(prompts, prompts[1:] + prompts[:1]))
    cycle = [256, 512, 768]
    assert sum(o for _, o in table) / len(table) == 512
    for i in range(192):                 # a client keeps its prompt length
        mine = [table[i + 192 * t] for t in range(3)]
        assert len({p for p, _ in mine}) == 1
        assert sorted(o for _, o in mine) == cycle
        assert [cycle.index(o) for _, o in mine] == \
            [(cycle.index(mine[0][1]) + t) % 3 for t in range(3)]
    assert serve.client_schedule(TRAFFIC, 7, 1) == tuple(table[199])
    assert serve.client_schedule(TRAFFIC, 191, 0) == tuple(table[191])
    assert serve.client_schedule(TRAFFIC, 4, 0) == (4096, 14)    # staggered


@pytest.fixture(scope="module")
def played():
    """PLAYED steps of the table on the toy model for two seeds."""
    return [conv_play.play(conv_play.tiny(CONFIG), TRAFFIC, seed, PLAYED)
            for seed in (11, 3_000_000_019)]


def test_schedule_is_the_same_for_every_seed(played):
    (a, shapes_a, faults_a), (b, shapes_b, faults_b) = played
    rows = [conv_play.rows_of(loop) for loop in (a, b)]
    assert rows[0] == rows[1] and shapes_a == shapes_b
    assert not faults_a and not faults_b
    ids_a, ids_b = (list(loop.prompts.values()) for loop in (a, b))
    assert all(x != y for x, y in zip(ids_a, ids_b))
    # 192 clients, and the first of them already on its second request
    assert len({tuple(p[:8]) for p in ids_a}) == len(ids_a) == 193
    assert all(len(s.decode_lens) == s.decode_rows for s in a.steps)
    # ... and the recorded schedule begins with what was just played
    with open(conv_play.SCHEDULE) as f:
        recorded = json.load(f)
    assert recorded["steps"][:PLAYED] == rows[0]
    assert recorded["shapes"][:PLAYED] == shapes_a


def test_no_step_shape_first_appears_after_warmup():
    """On the schedule recorded once on the CPU (a long play of the same
    loop; ``test_schedule_is_the_same_for_every_seed`` holds its start to
    what the code plays today)."""
    with open(conv_play.SCHEDULE) as f:
        recorded = json.load(f)
    warm = TRAFFIC["warmup_steps"]
    steps = recorded["steps"]
    shapes = [json.dumps([shape, step[0] > 0])
              for shape, step in zip(recorded["shapes"], steps)]
    # past the first fill (192 prompts of 1 152 at 1 024 a step); room
    assert 216 < warm < len(shapes) - 1200
    assert set(shapes[warm:]) <= set(shapes[:warm])
    # nothing is preempted at the configured pool; it fills past 60 %
    blocks = CONFIG["engine"]["num_blocks"]
    assert 0.60 * blocks < max(s[3] for s in steps) < blocks - 1
    # some 45 % of steps carry prompt chunks beside the decode rows
    mixed = sum(1 for s in steps[warm:] if s[1]) / len(steps[warm:])
    assert 0.35 < mixed < 0.55
    assert {s[1] for s in steps if s[1]} <= {512, 1024}
    assert max(s[0] for s in steps) == 192


# ---- the job, end to end on a tiny cell --------------------------------

def test_a_tiny_cell_runs_and_reports_its_counters():
    traffic = {"clients": 3, "warmup_steps": 4, "why": "a test",
               "table": [[24, 13], [16, 9], [8, 14], [24, 8], [8, 16],
                         [16, 11]]}
    out = serve_conv.run(tiny_job(), traffic, seed=3, seconds=0.5,
                         log=lambda m: None)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["e2e"]["serve_tok_per_s"] > 0
    assert "ttft_p50_ms" not in out["e2e"]
    c = out["counters"]
    assert c["probe_rel_l2"] < 1e-5 and c["steps"] == len(out["steps"])
    moe = c["moe_window"]              # two expert layers, top 2 of 8
    assert moe["mixed"]["rows_routed_here"] == 2 * 2 * moe["mixed"]["rows"]
    assert c["expert_rows_per_step"] == pytest.approx(
        2 * moe["mixed"]["rows"] / (moe["mixed"]["calls"] * 8))
    assert all(len(s.decode_lens) == s.decode_rows for s in out["steps"])
    assert "moe_traced" not in c                # an untraced run
    assert "prefill_segments_carried_share" not in c
    assert serve_conv.carried_share(None) is None


def test_the_lower_precision_control_reads_not_correct():
    """``tools/probe_readings.py`` takes this job too: the engine passes
    the comparison that decides ``correct``; the reference with its
    matrices rounded to 3 mantissa bits does not."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import probe_readings
    got = probe_readings.readings(tiny_job(max_batch=2), {"table": [[24, 4]]},
                                  serve_conv, 5)
    assert got["limit"] == serve_conv.LOGITS_TOL
    assert got["engine"]["correct"] and max(got["engine"]["rel_l2"]) < 1e-4
    assert not got["rounded_reference"]["correct"]
    assert min(got["rounded_reference"]["rel_l2"]) > got["limit"]


def test_probe_fails_on_wrong_logits(monkeypatch):
    from benchmark.reference import lfm2_moe
    real = lfm2_moe.logits
    monkeypatch.setattr(lfm2_moe, "logits",
                        lambda *a, **k: np.roll(real(*a, **k), 1, axis=0))
    config = tiny_job(max_batch=2)
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_conv.build_server(config, 5, workdir)
        try:
            probe = serve_conv.probe_engine(server, config,
                                            {"table": [[24, 4]]}, 5)
            with pytest.raises(AssertionError, match="reference"):
                serve_conv.compare_probe(server.engine.target, config,
                                         probe, tol=1e-4)
        finally:
            server.close()


def test_a_program_without_the_arch_fails_at_once(monkeypatch):
    """The parent under this PR's benchmark files: an ImportError before
    any weight is drawn."""
    from paddle_tpu.inference import decoder
    monkeypatch.setattr(decoder, "ARCHS", {"afmoe": ("gqa", "sandwich"),
                                           "joyai_llm_flash": ("mla",
                                                               "pre_norm")})
    with pytest.raises(ImportError, match="lfm2_moe"):
        serve_conv.build_server(CONFIG, 1, "/nonexistent")
    monkeypatch.delattr(decoder, "ARCHS")
    with pytest.raises(ImportError, match="lfm2_moe"):
        serve_conv.build_server(CONFIG, 1, "/nonexistent")


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
    out = serve_conv.run(CONFIG, TRAFFIC, seed=1, seconds=1.0)
    assert seen["build"] is serve_conv.build_server
    assert serve_arch.build_server is before
    assert seen["kw"]["logits_tol"] == serve_conv.LOGITS_TOL
    assert seen["experts"] == 64
    assert out["counters"] == {}


# ---- the readers, on planted data worked by hand ------------------------

@pytest.fixture(scope="module")
def planted():
    trace = xplane.summarize(FIXTURE)
    return planted_conv.plant({"trace": trace, "counters": {},
                               "series": {}, "e2e": {}})


@pytest.mark.parametrize("metric", sorted(planted_conv.PLANTED_VALUES))
def test_cell_readers_on_planted_data(planted, metric):
    got = cells.read_layer_metric(metric, planted)
    assert got["value"] == pytest.approx(
        planted_conv.PLANTED_VALUES[metric], rel=1e-9)
    assert got["unit"] == _entry("per_layer", metric)["unit"]
    empty = dict(planted, trace=None, counters={}, steps=[], series={})
    assert cells.read_layer_metric(metric, empty) is None


def test_kv_layers_roofline_by_hand():
    config = planted_conv.config()
    assert kv_layers_attn_roofline.kv_layers(config) == 2
    assert kv_layers_attn_roofline.page_bytes(config) \
        == 2 * 4 * 16 * 128 * 2 == 32768
    assert kv_layers_attn_roofline.row_bytes(config, 1000) == 2 * 63 * 32768
    assert kv_layers_attn_roofline.row_bytes(config, 16) == 2 * 32768
    assert planted_conv.PLANTED_VALUES["kv_layers_attn_roofline"] == \
        pytest.approx(6.29, abs=0.005)
    assert planted_conv.PLANTED_VALUES["gmm_busy_chat_roofline"] == \
        pytest.approx(80.16, abs=0.005)
    # the launch pattern is this cell's decode-shaped launch alone: four
    # stored rows, eight query rows a tile, 128 columns
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", "kv_layers_attn_roofline.json")))
    pat = re.compile(spec["args"]["pattern"])
    assert pat.match("mosaic:fwd_bf16_192_4_8_128_")
    assert not pat.match("mosaic:fwd_bf16_208_4_512_128_")        # mixed
    assert not pat.match("mosaic:fwd_bf16_32_8_6_128_")          # trinity's
    assert not pat.match("mosaic:fwd_bf16_64_1_32_512_")         # joyai's
    # a mixed step's rows are not counted, nor steps past the profile
    run = planted_conv.plant({"trace": {"op_seconds": {}}, "counters": {},
                              "series": {}})
    one = kv_layers_attn_roofline.read(run, spec["args"]["pattern"])
    run["trace"]["steps"] = 1
    assert kv_layers_attn_roofline.read(run, spec["args"]["pattern"]) == one
    run["steps"] = run["steps"][1:]
    assert kv_layers_attn_roofline.read(run, spec["args"]["pattern"]) is None
    # a program whose configuration has no stored row gives the reader nothing
    other = dict(planted_conv.plant({"trace": {"op_seconds": {}},
                                     "counters": {}, "series": {}}),
                 config={"x": 1})
    assert kv_layers_attn_roofline.read(other, spec["args"]["pattern"]) \
        is None


def test_conv_span_metric_reads_the_fabricated_session(session):
    """``conv_ms_per_step``: the ``conv`` spans of a round, mean over the
    whole rounds (the fabricated session's two, with 3 + 2 and 4 ms); a
    program without the span reads 0 there and nothing untraced."""
    assert cells.read_layer_metric("conv_ms_per_step",
                                   {"trace": {"steps": 2}}) \
        == {"value": 0.0, "unit": "ms"}
    for ts, dur, rnd in ((0.015, 0.003, 1), (0.020, 0.002, 1),
                         (0.206, 0.004, 2), (0.302, 0.009, 3)):
        session.events.append({"name": "conv", "ph": "X", "ts": ts,
                               "dur": dur, "args": {"round": rnd,
                                                    "parent": "model"}})
        session.events.append({"name": "conv.mix", "ph": "X", "ts": ts,
                               "dur": dur / 2, "args": {"round": rnd,
                                                        "parent": "conv"}})
    got = cells.read_layer_metric("conv_ms_per_step",
                                  {"trace": {"steps": 2}})
    assert got == {"value": pytest.approx(4.5), "unit": "ms"}
    assert cells.read_layer_metric("conv_ms_per_step",
                                   {"trace": None}) is None


def test_carried_share_sums_the_gauge_of_a_session(session):
    for segs, carried in ((1, 0), (2, 1), (0, 0), (1, 1)):
        session.events.append({"name": "slot_state", "ph": "C", "ts": 0.1,
                               "args": {"prompt_segments": segs,
                                        "prompt_segments_carried": carried,
                                        "segments": segs + 5}})
    session.events.append({"name": "paged_attn", "ph": "C", "ts": 0.1,
                           "args": {"prompt_segments": 9}})
    assert serve_conv.carried_share(session) == 50.0
    session.events.clear()
    assert serve_conv.carried_share(session) is None
