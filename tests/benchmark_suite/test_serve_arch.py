"""The ``trinity-large.mixed-queue`` cell, rehearsed on the CPU: its
configuration against the catalog's keys, its traffic table and schedule,
the job (``benchmark/jobs/serve_arch.py``) end to end on a tiny ``afmoe``,
and its readers on planted data worked by hand
(``tests/benchmark_suite/planted_afmoe.py``).
"""
import copy
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
from benchmark.jobs import serve, serve_arch  # noqa: E402
from benchmark.layer_metrics import (gmm_roofline,  # noqa: E402
                                     paged_attn_window_roofline)
from tests.benchmark_suite import planted_afmoe  # noqa: E402

CELL = "trinity-large.mixed-queue"
MANIFEST = cells.load_manifest(ROOT)
LOADED = cells.load_cell(CELL, MANIFEST, ROOT)
CONFIG, TRAFFIC = LOADED["config"], LOADED["traffic"]
FIXTURE = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb")
SCHEDULE = os.path.join(ROOT, "benchmark", "testdata",
                        "mixed-queue.schedule.json")
# the published config.json (the catalog's copy), the keys that say
# something about the model's shape
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
    "model_type": "afmoe", "moe_intermediate_size": 3072,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
    "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
    "num_experts_per_tok": 4, "num_hidden_layers": 60,
    "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}
PLAYED = 10


def tiny(config=CONFIG, **changes):
    """The cell's configuration at toy widths: same engine sizes (slots,
    pool, budget), which with the table make the schedule."""
    out = dict(copy.deepcopy(config), hidden_size=32, num_attention_heads=2,
               num_key_value_heads=1, head_dim=16, intermediate_size=32,
               num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
               vocab_size=97, weight_dtype="float32", layers_run=[5, 11])
    out["deployment_cut"] = dict(config["deployment_cut"],
                                 num_experts_published=8, expert_offset=0)
    out["engine"] = dict(config["engine"], kv_dtype="float32")
    out.update(changes)
    return out


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


def test_config_cut_is_a_whole_period_and_a_stated_share():
    pattern = ["sliding_attention"] * 3 + ["full_attention"]
    assert CONFIG["layer_types"] == pattern * 15           # as published
    assert serve_arch.layer_types(CONFIG) == \
        ["sliding_attention"] + pattern                    # dense, a period
    assert len(CONFIG["layers_run"]) == CONFIG["num_hidden_layers"] == 5
    dense = [i for i in CONFIG["layers_run"] if i < 6]
    assert len(dense) == CONFIG["num_dense_layers"] == 1
    cut = CONFIG["deployment_cut"]
    assert cut["chips_per_layer"] * CONFIG["num_experts"] == \
        cut["num_experts_published"] == 256
    assert cut["expert_offset"] % CONFIG["num_experts"] == 0
    assert CONFIG["vocab_size"] * 8 == 200192
    assert sorted(CONFIG["reduced"]) == sorted(next(
        c["reduced"] for c in MANIFEST["configs"]
        if c["name"] == "trinity-large-ep8"))
    with tempfile.TemporaryDirectory() as workdir:
        spec = serve_arch.server_spec(CONFIG, 2**31 + 9, workdir)
    assert spec["num_experts"] == 256 and spec["experts_held"] == 32
    assert spec["expert_offset"] == 64 and spec["arch"] == "afmoe"
    assert spec["num_experts_per_tok"] == 4 and spec["vocab_size"] == 25024
    assert 0 <= spec["model_seed"] < 2**31


def test_config_memory_arithmetic():
    d, im, hd = 3072, 3072, 128
    attn = d * (48 + 8 + 8 + 48) * hd + 48 * hd * d
    moe = attn + 3 * d * im + d * 256 + 32 * 3 * d * im
    dense = attn + 3 * d * 12288
    total = 4 * moe + dense + d * 25024
    assert round(total / 1e6) == 4245                      # 8.49 GB in bf16
    engine = CONFIG["engine"]
    per_token = 5 * 2 * 8 * hd * 2
    pool = engine["num_blocks"] * engine["block_size"] * per_token
    assert per_token == 20480 and round(pool / 1e9, 2) == 3.36
    most = 8 * (12288 + 128) + 24 * (2048 + 192)
    assert most == 153088 < (engine["num_blocks"] - 1) * engine["block_size"]
    assert engine["max_blocks_per_seq"] * engine["block_size"] >= 12288 + 128


# ---- the traffic -------------------------------------------------------

def test_traffic_table_is_the_issues():
    table = TRAFFIC["table"]
    assert len(table) == 64 and TRAFFIC["clients"] == 32
    assert table[0] == [6144, 128] and TRAFFIC["stagger_first_output"]
    long_ids = list(range(0, 32, 4))
    for half in (table[:32], table[32:]):
        assert sorted(half[i][0] for i in long_ids) == \
            sorted([6144, 8192, 10240, 12288] * 2)
        assert all(half[i][1] == 128 for i in long_ids)
        short = [half[i] for i in range(32) if i not in long_ids]
        assert sorted(p for p, _ in short) == \
            [512] * 12 + [1024] * 8 + [2048] * 4
        assert {o for _, o in short} == {64, 128, 192}
    assert all(p % 512 == 0 for p, _ in table)
    assert serve.client_schedule(TRAFFIC, 4, 1) == tuple(table[36])


@pytest.fixture(scope="module")
def played():
    """PLAYED steps of the table on the tiny model for two seeds, and the
    ragged layouts' segment lengths step by step."""
    from paddle_tpu.inference import paged_cache
    out = []
    real = paged_cache._RaggedLayout.__init__
    for seed in (11, 3_000_000_019):
        shapes = []

        def spy(self, cache, segments, **kw):
            real(self, cache, segments, **kw)
            shapes[-1] = [q for q in self.q_lens if q > 1]
        paged_cache._RaggedLayout.__init__ = spy
        try:
            with tempfile.TemporaryDirectory() as workdir:
                server = serve_arch.build_server(tiny(), seed, workdir)
                try:
                    loop = serve_arch.Loop(server, TRAFFIC, 97, seed)
                    for _ in range(PLAYED):
                        shapes.append("decode")
                        loop.step()
                    faults = loop.audit()
                finally:
                    server.close()
        finally:
            paged_cache._RaggedLayout.__init__ = real
        out.append((loop, shapes, faults))
    return out


def test_schedule_is_the_same_for_every_seed(played):
    (a, shapes_a, faults_a), (b, shapes_b, faults_b) = played
    rows = [[[s.decode_rows, s.prefill_tokens, s.emitted, s.blocks_live]
             for s in loop.steps] for loop in (a, b)]
    assert rows[0] == rows[1] and shapes_a == shapes_b
    assert not faults_a and not faults_b
    ids_a, ids_b = (list(loop.prompts.values()) for loop in (a, b))
    assert all(x != y for x, y in zip(ids_a, ids_b))
    assert len({tuple(p[:8]) for p in ids_a}) == len(ids_a)
    # the loop's row lengths are the decode rows the step reported
    assert all(len(s.decode_lens) == s.decode_rows for s in a.steps)
    # ... and the recorded schedule begins with what was just played
    with open(SCHEDULE) as f:
        recorded = json.load(f)
    assert recorded["steps"][:PLAYED] == rows[0]
    assert recorded["shapes"][:PLAYED] == shapes_a


def test_no_step_shape_first_appears_after_warmup():
    """On the schedule recorded once on the CPU (a long play of the same
    loop: ``test_schedule_is_the_same_for_every_seed`` holds its start to
    what the code plays today). A step's programs are shaped by the lengths
    of its prompt chunks and by whether it has decode rows."""
    with open(SCHEDULE) as f:
        recorded = json.load(f)
    warm = TRAFFIC["warmup_steps"]
    steps = recorded["steps"]
    # ... (a launch with decode rows is another program than one without)
    shapes = [json.dumps([shape, step[0] > 0])
              for shape, step in zip(recorded["shapes"], steps)]
    assert 0 < warm < len(shapes) - 1000      # room for a 30 s window
    assert set(shapes[warm:]) <= set(shapes[:warm])
    # nothing is preempted at the configured pool
    assert max(s[3] for s in steps) < CONFIG["engine"]["num_blocks"] - 1
    # about one step in three carries prompt chunks, two in three do not
    mixed = sum(1 for s in steps[warm:] if s[1]) / len(steps[warm:])
    assert 0.35 < mixed < 0.5
    assert {s[1] for s in steps if s[1]} == {512, 1024, 1536, 2048}


# ---- the job, end to end on a tiny cell --------------------------------

def test_a_tiny_cell_runs_and_reports_its_counters():
    config = tiny(sliding_window=8)
    config["engine"] = dict(config["engine"], max_batch=4, block_size=4,
                            num_blocks=120, max_blocks_per_seq=16,
                            prefill_token_budget=16)
    traffic = {"clients": 3, "warmup_steps": 4, "why": "a test",
               "table": [[24, 3], [16, 5], [8, 4], [24, 3], [8, 6], [16, 3]]}
    out = serve_arch.run(config, traffic, seed=3, seconds=0.5,
                         log=lambda m: None, logits_tol=1e-4)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["e2e"]["serve_tok_per_s"] > 0 and out["e2e"]["itl_p95_ms"] > 0
    assert "ttft_p50_ms" not in out["e2e"]
    # the per-layer tail is the same percentile of the same gaps
    from benchmark.metrics import percentile
    assert percentile(out["series"]["token_gap_ms"], 95) == \
        out["e2e"]["itl_p95_ms"]
    c = out["counters"]
    assert c["probe_rel_l2"] < 1e-5 and c["steps"] == len(out["steps"])
    moe = c["moe_window"]
    assert moe["mixed"]["calls"] + moe["decode"]["calls"] >= c["steps"]
    # one expert layer, every expert held: each row sends top-k
    assert moe["mixed"]["rows_routed_here"] == 2 * moe["mixed"]["rows"]
    assert c["expert_rows_per_step"] == pytest.approx(
        2 * moe["mixed"]["rows"] / (moe["mixed"]["calls"] * 8))
    assert "moe_traced" not in c                # an untraced run


def test_the_lower_precision_control_reads_not_correct():
    """``tools/probe_readings.py``: the engine passes the comparison that
    decides ``correct``; the reference with its matrices rounded to 3
    mantissa bits, handed to the same comparison, does not."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import probe_readings
    config = tiny(sliding_window=8)
    config["engine"] = dict(config["engine"], max_batch=2, block_size=4,
                            num_blocks=60, max_blocks_per_seq=12,
                            prefill_token_budget=16)
    got = probe_readings.readings(config, {"table": [[24, 4]]}, serve_arch, 5)
    assert got["engine"]["correct"] and max(got["engine"]["rel_l2"]) < 1e-4
    assert not got["rounded_reference"]["correct"]
    assert min(got["rounded_reference"]["rel_l2"]) > got["limit"]


def test_probe_fails_on_wrong_logits(monkeypatch):
    from benchmark.reference import afmoe
    real = afmoe.logits
    monkeypatch.setattr(afmoe, "logits",
                        lambda *a, **k: np.roll(real(*a, **k), 1, axis=0))
    config = tiny(sliding_window=8)
    config["engine"] = dict(config["engine"], max_batch=2, block_size=4,
                            num_blocks=60, max_blocks_per_seq=12,
                            prefill_token_budget=16)
    with tempfile.TemporaryDirectory() as workdir:
        server = serve_arch.build_server(config, 5, workdir)
        try:
            with pytest.raises(AssertionError, match="reference"):
                serve_arch.check_probe(server, config,
                                       {"table": [[24, 4]]}, 5, tol=1e-4)
        finally:
            server.close()


# ---- the readers, on planted data worked by hand ------------------------

@pytest.fixture(scope="module")
def planted():
    trace = xplane.summarize(FIXTURE)
    return planted_afmoe.plant({"trace": trace, "counters": {}, "series": {},
                                "e2e": {}})


@pytest.mark.parametrize("metric", [m for m in LOADED["per_layer"]
                                    if m in planted_afmoe.PLANTED_VALUES])
def test_cell_readers_on_planted_data(planted, metric):
    got = cells.read_layer_metric(metric, planted)
    want = planted_afmoe.PLANTED_VALUES[metric]
    if want is None:                                  # gmm_busy_share
        want = 100.0 * planted_afmoe.GMM_SECONDS / planted["trace"]["busy_s"]
    assert got["value"] == pytest.approx(want, rel=1e-9)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert got["unit"] == entry["unit"] and entry["workloads"] == [CELL]
    empty = dict(planted, trace=None, counters={}, steps=[], series={})
    assert cells.read_layer_metric(metric, empty) is None


def test_rooflines_by_hand():
    config = planted_afmoe.config()
    assert gmm_roofline.expert_bytes(config) == 3 * 3072 * 3072 * 2
    # 12.508 ms of bytes against 1.224 ms of FLOPs
    least = gmm_roofline.least_seconds(config, planted_afmoe.PEAKS, 4260, 180)
    assert least == pytest.approx(10_244_505_600 / 819e9)
    # compute-bound once an expert's rows pass the ridge (240 rows a block)
    assert gmm_roofline.least_seconds(
        config, planted_afmoe.PEAKS, 32 * 1000, 32) == pytest.approx(
            32000 * 6 * 3072 * 3072 / 197e12)
    assert paged_attn_window_roofline.page_bytes(config) == 65536
    assert [paged_attn_window_roofline.row_pages(config, n)
            for n in (100, 4096, 5000, 12416)] == \
        [35, 1280, 4 * 256 + 313, 4 * 256 + 776]
    assert planted_afmoe.PLANTED_VALUES["gmm_roofline"] == \
        pytest.approx(312.7, abs=0.05)
    assert planted_afmoe.PLANTED_VALUES["paged_attn_window_roofline"] == \
        pytest.approx(10.61, abs=0.005)


def test_window_gauge_sums_the_session(session):
    session.gauge("paged_attn", {"grid_steps": 9, "pages_per_step": 8,
                                 "heads_per_step": 8, "pages_in_context": 400,
                                 "pages_behind_window": 100})
    session.gauge("paged_attn", {"grid_steps": 9, "pages_per_step": 8,
                                 "heads_per_step": 8})        # no window
    session.gauge("paged_attn", {"grid_steps": 9, "pages_per_step": 8,
                                 "heads_per_step": 8, "pages_in_context": 600,
                                 "pages_behind_window": 150})
    assert serve_arch.window_gauge(session) == (1000, 250)
    assert serve_arch.window_gauge(None) == (0, 0)


def test_moe_delta_is_per_kind():
    before = {k: {"calls": 1, "rows": 32, "layer_calls": 4,
                  "rows_routed_here": 60, "experts_hit": 50, "load": {}}
              for k in ("decode", "mixed")}
    after = copy.deepcopy(before)
    after["mixed"].update(calls=3, rows=4192, layer_calls=12,
                          rows_routed_here=8460, experts_hit=306)
    assert serve_arch.moe_delta(after, before) == {
        "decode": dict.fromkeys(
            ("calls", "rows", "layer_calls", "rows_routed_here",
             "experts_hit"), 0),
        "mixed": {"calls": 2, "rows": 4160, "layer_calls": 8,
                  "rows_routed_here": 8400, "experts_hit": 256}}
