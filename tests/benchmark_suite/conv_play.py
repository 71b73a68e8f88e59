"""Playing the ``lfm2-24b.busy-chat`` table on the CPU at toy widths and the
cell's own engine sizes (slots, pool, budget: with the table they make the
schedule): what ``test_serve_conv.py`` plays the start of, and what recorded
``benchmark/testdata/busy-chat.schedule.json``:

    JAX_PLATFORMS=cpu python -m tests.benchmark_suite.conv_play 2600
"""
import copy
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "lfm2-24b.busy-chat"
SCHEDULE = os.path.join(ROOT, "benchmark", "testdata",
                        "busy-chat.schedule.json")
WHAT = ("the busy-chat table played once on the CPU through "
        "serve_arch.Loop at toy widths and the cell's own engine sizes "
        "(tests/benchmark_suite/test_serve_conv.py plays the start again "
        "and holds it to this): per step [decode rows, prompt tokens, "
        "tokens emitted, blocks live], and the step's shape: 'decode' or "
        "the lengths of its prompt chunks in launch order")


def tiny(config, **changes):
    """The cell's configuration at toy widths (a dense conv layer, an
    attention layer, a conv layer), the engine's sizes kept."""
    out = dict(copy.deepcopy(config), hidden_size=32, num_attention_heads=2,
               num_key_value_heads=1, intermediate_size=32,
               moe_intermediate_size=16, num_experts=8,
               num_experts_per_tok=2, vocab_size=97,
               weight_dtype="float32", layers_run=[1, 2, 3])
    out["engine"] = dict(config["engine"], kv_dtype="float32")
    out.update(changes)
    return out


def play(config, traffic, seed, steps):
    """(loop, shapes, faults) of ``steps`` steps of ``traffic`` on the toy
    model: ``shapes`` are the ragged layouts' prompt-chunk lengths step by
    step ('decode' where a step packed nothing)."""
    from benchmark.jobs import serve_conv
    from paddle_tpu.inference import paged_cache
    real = paged_cache._RaggedLayout.__init__
    shapes = []

    def spy(self, cache, segments, **kw):
        real(self, cache, segments, **kw)
        shapes[-1] = [q for q in self.q_lens if q > 1]
    paged_cache._RaggedLayout.__init__ = spy
    try:
        with tempfile.TemporaryDirectory() as workdir:
            server = serve_conv.build_server(config, seed, workdir)
            try:
                loop = serve_conv.Loop(server, traffic,
                                       config["vocab_size"], seed)
                for _ in range(steps):
                    shapes.append("decode")
                    loop.step()
                faults = loop.audit()
            finally:
                server.close()
    finally:
        paged_cache._RaggedLayout.__init__ = real
    return loop, shapes, faults


def rows_of(loop):
    return [[s.decode_rows, s.prefill_tokens, s.emitted, s.blocks_live]
            for s in loop.steps]


if __name__ == "__main__":
    import tests.conftest  # noqa: F401  (the CPU platform, as the tests)
    from benchmark import cells
    loaded = cells.load_cell(CELL)
    loop, shapes, faults = play(tiny(loaded["config"]), loaded["traffic"],
                                11, int(sys.argv[1]))
    assert not faults, faults
    with open(sys.argv[2] if len(sys.argv) > 2 else SCHEDULE, "w") as f:
        json.dump({"what": WHAT, "steps": rows_of(loop), "shapes": shapes}, f)
