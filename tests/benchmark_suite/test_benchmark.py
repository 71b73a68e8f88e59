"""The on-chip benchmark, rehearsed on the CPU at tiny widths.

What runs here is everything but the chip: the manifest's form, the traffic
tables played against a tiny model (the sequence of batches depends on the
table and the scheduler, not on the widths), the metric arithmetic, the two
plain references against the system, the trace reduction on a small recorded
trace, and the command line's refusal to run without a TPU.
"""
import copy
import functools
import json
import os
import re
import subprocess
import sys
import tempfile

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import cells, metrics, xplane  # noqa: E402
from benchmark.jobs import serve, train  # noqa: E402

MANIFEST = cells.load_manifest(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FIXTURE = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb")
PLAYED_STEPS = 200

TINY_GPT = {"n_layers": 1, "d_model": 32, "n_heads": 2, "d_head": 16,
            "d_ff": 64, "n_vocab": 211}
TINY_MISTRAL = {"hidden_size": 64, "intermediate_size": 128,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "vocab_size": 256}
TINY_BATCH = {"batch": 4, "seq": 32, "warmup_steps": 1}


def _config(name, **changes):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config.update(changes)
    return config


def tiny_gpt():
    config = _config("gpt3-6.7b", **TINY_GPT)
    # same slots, pool and budget as on the chip: they make the schedule
    config["engine"] = dict(config["engine"], kv_dtype="float32")
    return config


def tiny_mistral(layers, mesh):
    config = _config("mistral-7b-1chip", num_hidden_layers=layers, mesh=mesh,
                     **TINY_MISTRAL)
    config["trainer"] = dict(config["trainer"], compute_dtype="float32",
                             moments_dtype="float32")
    return config


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


# --------------------------------------------------------------------------
# (a) the manifest is well formed
# --------------------------------------------------------------------------

def _all_entries():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[key]:
            yield key, entry


@pytest.mark.parametrize("key,entry", list(_all_entries()),
                         ids=lambda v: v["name"] if isinstance(v, dict) else v)
def test_manifest_names_and_units(key, entry):
    assert NAME.match(entry["name"])
    for field in ("config", "traffic", "moves"):
        assert NAME.match(entry.get(field, "x"))
    for word in entry.get("reduced", []):
        assert NAME.match(word)
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for field in ("why", "layer", "source"):
        text = entry.get(field, "x")
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_manifest_is_unique_and_small():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[key]]
        assert len(names) == len(set(names))
    names = [e["name"] for e in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert 0 < MANIFEST["end_to_end"][-1]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in MANIFEST["end_to_end"])


def test_files_under_paths_have_plain_names():
    plain = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        assert plain.match(path) and len(path) <= 200
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert plain.match(rel), rel
    assert all(not w.startswith("/") and ".." not in w
               for w in MANIFEST["command"])


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_files_and_metrics(cell):
    loaded = cells.load_cell(cell, MANIFEST, ROOT)
    config, traffic = loaded["config"], loaded["traffic"]
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == loaded["cell"]["config"])
    assert entry["file"].startswith(tuple(MANIFEST["paths"]))
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert hasattr(loaded["job"], "run")
    assert "warmup_steps" in traffic and traffic["why"]
    # setup_s, one more end-to-end metric, one per-layer metric
    assert "setup_s" in loaded["end_to_end"]
    assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_reported_metric(metric):
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert metric["moves"] in e2e
    every = [w["name"] for w in MANIFEST["workloads"]]
    for cell in metric.get("workloads", every):
        assert cell in e2e[metric["moves"]].get("workloads", every)
    spec_path = os.path.join(ROOT, "benchmark", "layer_metrics",
                             metric["name"] + ".json")
    with open(spec_path) as f:
        spec = json.load(f)
    assert spec["unit"] == metric["unit"]


# --------------------------------------------------------------------------
# (b) the shape of the traffic does not depend on the seed
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _played(traffic_name, seed):
    """PLAYED_STEPS steps of a serving table on the tiny model, the probe
    first, as a run plays them."""
    config, traffic = tiny_gpt(), _traffic(traffic_name)
    with tempfile.TemporaryDirectory() as workdir:
        server = serve.build_server(config, seed, workdir)
        try:
            err = serve.check_probe(server, config, traffic, seed, tol=1e-4)
            loop = serve.ClosedLoop(server, traffic, config["n_vocab"], seed)
            for _ in range(PLAYED_STEPS):
                loop.step()
            faults = loop.audit()
        finally:
            server.close()
    return loop, err, faults


@pytest.mark.parametrize("traffic_name", ["chat", "doc"])
def test_schedule_is_the_same_for_every_seed(traffic_name):
    a, _, faults_a = _played(traffic_name, 11)
    b, _, faults_b = _played(traffic_name, 3_000_000_019)
    rows = [[(s.decode_rows, s.prefill_tokens, s.emitted, s.blocks_live)
             for s in loop.steps] for loop in (a, b)]
    assert rows[0] == rows[1]
    assert not faults_a and not faults_b
    assert [(r.prompt_len, r.out_len) for r in a.requests] == \
        [(r.prompt_len, r.out_len) for r in b.requests]
    # ... and the seed does draw the content
    ids_a, ids_b = (list(loop.prompts.values()) for loop in (a, b))
    assert all(x != y for x, y in zip(ids_a, ids_b))
    assert len({tuple(p[:8]) for p in ids_a}) == len(ids_a)   # no shared prefix


@pytest.mark.parametrize("traffic_name", ["chat", "doc"])
def test_no_step_shape_first_appears_after_warmup(traffic_name):
    """A step's programs are shaped by its prefill tokens and by whether it
    has decode rows (the engine pads them to its slots); the count of live
    decode rows is not a shape."""
    loop, _, _ = _played(traffic_name, 11)
    warm = _traffic(traffic_name)["warmup_steps"]
    assert 0 < warm < PLAYED_STEPS
    shapes = [(s.decode_rows > 0, s.prefill_tokens) for s in loop.steps]
    assert set(shapes[warm:]) <= set(shapes[:warm])
    assert max(s.blocks_live for s in loop.steps) < 3071   # nothing preempted


# --------------------------------------------------------------------------
# (c) metric arithmetic on a hand-made event list
# --------------------------------------------------------------------------

def _events():
    S, R = metrics.Step, metrics.Request
    steps = [S(0.0, 1.0, 0, 100, 1), S(1.0, 2.0, 1, 50, 2),
             S(2.0, 4.0, 2, 0, 2), S(4.0, 5.0, 2, 0, 2)]
    requests = [R(100, 4, 0.0, [1.0, 2.0, 4.0, 5.0]),
                R(50, 3, 1.5, [2.0, 4.0, 5.0])]
    return steps, requests


@pytest.mark.parametrize("name,t_open,want", [
    ("serve_tok_per_s", 0.0, (101 + 52 + 2 + 2) / 5.0),
    ("serve_tok_per_s", 1.0, (52 + 2 + 2) / 4.0),     # step 0 ended at t_open
    ("ttft_p50_ms", 0.0, 750.0),                      # 1000 and 500
    ("ttft_p50_ms", 1.0, 500.0),
    ("itl_p95_ms", 0.0, 2000.0),                      # 1000 2000 1000 2000 1000
    ("itl_p95_ms", 4.0, 1000.0),                      # the two that end at 5.0
])
def test_serve_metric_arithmetic(name, t_open, want):
    steps, requests = _events()
    got = metrics.serve_metrics(steps, requests, t_open)
    assert got[name] == pytest.approx(want)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4], 50, 2.5), ([5], 95, 5), (list(range(101)), 95, 95.0),
    ([3, 1, 2], 0, 1), ([3, 1, 2], 100, 3)])
def test_percentile_is_numpys(values, q, want):
    assert metrics.percentile(values, q) == pytest.approx(want)
    assert metrics.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_train_metric_arithmetic():
    got = metrics.train_metrics([1.0, 2.0, 3.5, 4.0], 100, t_open=2.0)
    assert got["train_tok_per_s"] == pytest.approx(200 / 2.0)
    with pytest.raises(ValueError):
        metrics.train_metrics([1.0], 100, t_open=2.0)


def test_client_schedule_is_the_table():
    traffic = _traffic("chat")
    table = traffic["table"]
    assert len(table) == 64 and traffic["clients"] == 32
    assert np.mean([p for p, _ in table]) == 224
    assert np.mean([o for _, o in table]) == 112
    assert serve.client_schedule(traffic, 3, 1) == tuple(table[35])
    assert serve.client_schedule(traffic, 3, 2) == tuple(table[3])
    prompt, out = serve.client_schedule(traffic, 0, 0)
    assert (prompt, out) == (table[0][0], -(-table[0][1] // 32))
    doc = _traffic("doc")
    assert serve.client_schedule(doc, 5, 7) == tuple(doc["table"][5])
    need = sum(-(-(p + o) // 16) for p, o in doc["table"])
    assert need < 3071                       # all 24 fit the pool at once


# --------------------------------------------------------------------------
# (d) the plain references agree with the system at a tiny size
# --------------------------------------------------------------------------

@pytest.mark.parametrize("traffic_name", ["chat", "doc"])
def test_gpt3_reference_agrees_with_the_engine(traffic_name):
    _, err, _ = _played(traffic_name, 11)
    assert err <= 1e-4          # float32 pool, "highest" matmuls: order only


def test_probe_check_fails_on_wrong_logits(monkeypatch):
    from benchmark.reference import gpt3
    real = gpt3.logits
    monkeypatch.setattr(gpt3, "logits",
                        lambda *a, **k: np.roll(real(*a, **k), 1, axis=0))
    config, traffic = tiny_gpt(), _traffic("chat")
    with tempfile.TemporaryDirectory() as workdir:
        server = serve.build_server(config, 5, workdir)
        try:
            with pytest.raises(AssertionError, match="reference"):
                serve.check_probe(server, config, traffic, 5, tol=1e-4)
        finally:
            server.close()


@pytest.mark.parametrize("layers,mesh,chips", [
    (2, {"dp": 1}, 1), (4, {"pp": 2, "mp": 2}, 4)], ids=["1chip", "pp2mp2"])
def test_mistral_reference_agrees_with_the_trainer(layers, mesh, chips):
    out = train.run(tiny_mistral(layers, mesh), TINY_BATCH, seed=2**31 + 5,
                    seconds=0.2, chips=chips, log=lambda m: None,
                    loss_tol=1e-4)
    c = out["counters"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert abs(c["first_loss"] - c["reference_loss"]) <= 1e-4
    assert out["e2e"]["train_tok_per_s"] > 0
    assert len(out["series"]["train_step_ms"]) == out["attempted"]


def test_required_flops_follow_the_published_widths():
    config = _config("mistral-7b-1chip")
    per_layer = 2 * 4096 ** 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    want = 6 * (2 * per_layer + 32000 * 4096) + 6 * 2 * 4096 * 2048
    assert train.required_flops_per_token(config, 2048) == want


# --------------------------------------------------------------------------
# (e) the command line has no CPU continuation
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cell", [MANIFEST["workloads"][0]["name"], "nosuch"])
def test_run_without_a_tpu_prints_no_metric(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and "{" not in proc.stdout


# --------------------------------------------------------------------------
# (f) a cell is one manifest entry and new files
# --------------------------------------------------------------------------

def test_a_throwaway_cell_loads_and_plays(tmp_path):
    config = dict(tiny_gpt(), name="tiny")
    traffic = {"clients": 3, "warmup_steps": 4, "why": "a test",
               "table": [[8, 3], [16, 5], [8, 4], [24, 3], [8, 6], [16, 3]]}
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(config))
    (tmp_path / "burst.json").write_text(json.dumps(traffic))
    manifest = copy.deepcopy(MANIFEST)
    manifest["configs"].append({"name": "tiny", "file": "configs/tiny.json",
                                "source": "none", "reduced": [], "why": "t"})
    manifest["workloads"].append({"name": "tiny.burst", "config": "tiny",
                                  "traffic": "burst", "chips": 1, "why": "t"})
    cell = cells.load_cell("tiny.burst", manifest, str(tmp_path),
                           traffic_dir=str(tmp_path))
    assert cell["job"] is serve and "setup_s" in cell["end_to_end"]
    out = cell["job"].run(cell["config"], cell["traffic"], seed=3,
                          seconds=0.3, log=lambda m: None, logits_tol=1e-4)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["e2e"]["serve_tok_per_s"] > 0
    assert out["e2e"]["ttft_p50_ms"] > 0 and out["e2e"]["itl_p95_ms"] > 0
    assert out["counters"]["steps"] == len(out["series"]["rows_per_step"])


# --------------------------------------------------------------------------
# the trace reduction, on a small trace recorded on the chip
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trace():
    return xplane.summarize(FIXTURE)


def test_trace_busy_and_idle(trace):
    assert trace["devices"] == 1
    assert 0 < trace["busy_s"] < trace["window_s"]
    assert sum(trace["gap_seconds"].values()) == pytest.approx(
        trace["window_s"] - trace["busy_s"])
    assert sum(trace["op_seconds"].values()) == pytest.approx(trace["busy_s"])
    assert trace["launches"] == 6          # two programs in each of 3 steps
    assert trace["span_counts"] == {"bench.submit": 3, "bench.step": 3,
                                    "bench.collect": 3}


def test_trace_names_a_kernel_and_a_gap(trace):
    # the matmul+tanh program's fusion, three launches of ~19 us
    name = "convolution_tanh_fusion_f32_1024_1024_"
    assert 40e-6 < trace["op_seconds"][name] < 80e-6
    assert xplane.op_seconds(trace, "^convolution_tanh") == \
        trace["op_seconds"][name]
    # the device sits idle while the host sleeps 3 x 3 ms in bench.submit
    # and 3 x 2 ms in bench.collect
    assert 9e-3 < trace["gap_seconds"]["bench.submit"] < 12e-3
    assert 6e-3 < trace["gap_seconds"]["bench.collect"] < 8e-3
    assert trace["gap_seconds"]["bench.step"] < 3e-3
    top = xplane.breakdown(trace)
    assert len(top["device_ops"]) <= 10 and top["idle_gaps"][0][1] > 0


@pytest.mark.parametrize("hlo,want", [
    ('%fwd.1 = f32[32,32,1,128]{3,2,1,0:T(1,128)S(1)} custom-call(s32[32,128]'
     '{1,0} %a), custom_call_target="tpu_custom_call"',
     "mosaic:fwd_f32_32_32_1_128_"),
    ("%copy.4 = bf16[3072,2,32,16,128]{4,2,3,1,0:T(8,128)(2,1)} copy(bf16[3072"
     ",2,32,16,128]{4,3,2,1,0} %arrays_0_.1)", "copy_bf16_3072_2_32_16_128_"),
    ("%copy-start = (f32[1024,1024]{1,0}, u32[]{:S(2)}) copy-start(f32[1024,"
     "1024]{1,0} %a.1)", "copy-start_f32_1024_1024_"),
    ("%all-reduce-done.2 = f32[16]{0} all-reduce-done(f32[16]{0} %x)",
     "all-reduce-done_f32_16_"),
    ("jit_step(123)", "jit_step(123)")])
def test_short_names(hlo, want):
    assert xplane.short_name(hlo) == want
    assert bool(xplane.COLLECTIVE.match(want)) == want.startswith("all-")


def test_gaps_are_split_between_the_spans_they_cross():
    spans = [("bench.a", 10, 10), ("bench.b", 20, 10), ("bench.c", 50, 10)]
    got = list(xplane._gaps_by_span([(0, 5), (15, 25), (28, 55)], spans))
    assert got == [(xplane.NO_SPAN, 5), ("bench.a", 5), ("bench.b", 5),
                   ("bench.b", 2), ("bench.c", 5), (xplane.NO_SPAN, 20)]


def test_self_times_and_union():
    events = [("while", 0, 100), ("a", 10, 20), ("b", 40, 50), ("c", 200, 5)]
    assert dict(xplane._self_times(events)) == {
        "while": 30, "a": 20, "b": 50, "c": 5}
    assert xplane._merge([(3, 10), (0, 5), (20, 30)]) == [[0, 10], [20, 30]]


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_layer_metric_readers(metric, trace):
    """Every reader gives a number from a run that has what it reads, and
    nothing from a run that has not."""
    config = _config("gpt3-6.7b")
    S = metrics.Step
    full = {
        "series": {"rows_per_step": [40, 300], "blocks_live": [600, 700],
                   "decode_step_ms": [160.0, 170.0], "mixed_step_ms": [200.0],
                   "train_step_ms": [900.0, 910.0]},
        "counters": {"pool_blocks": 3072, "compiles_in_window": 0,
                     "flops_per_token": 3.5e9, "chips": 1,
                     "tokens_per_step": 32768},
        "e2e": {},
        "steps": [S(0, 1, 32, 0, 32, 600, 640), S(1, 2, 31, 256, 32, 700, 600)],
        "trace": dict(trace, steps=2, collective_s=1e-4, op_seconds=dict(
            trace["op_seconds"], **{"mosaic:fwd_f32_32_32_1_128_": 1e-3,
                                    "mosaic:fwd_f32_36_32_64_128_": 2e-3})),
        "config": config, "peaks": {"bf16_flops": 197e12,
                                    "hbm_bytes_per_s": 819e9},
    }
    got = cells.read_layer_metric(metric["name"], full)
    assert got["unit"] == metric["unit"] and np.isfinite(got["value"])
    empty = {"series": {}, "counters": {}, "e2e": {}, "steps": [],
             "trace": None, "config": config, "peaks": full["peaks"]}
    assert cells.read_layer_metric(metric["name"], empty) is None


def test_roofline_bytes_follow_the_pool_layout():
    from benchmark.layer_metrics import paged_attn_roofline as roof
    config = _config("gpt3-6.7b")
    # K and V, 32 heads, 16 positions of 128 bf16, 4 layers
    assert roof.kv_bytes_per_page(config) == 2 * 32 * 16 * 128 * 2 * 4
    run = {"trace": {"steps": 1, "op_seconds": {"mosaic:fwd_f32_32_32_1_128_":
                                                 0.1}},
           "steps": [metrics.Step(0, 1, 32, 0, 32, 0, 640)], "config": config,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    want = 100 * (640 * 1048576 / 819e9) / 0.1
    assert roof.read(run, pattern="^mosaic:fwd_") == pytest.approx(want)
