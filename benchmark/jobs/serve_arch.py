"""Serving job for a configuration whose file speaks its published
``config.json`` keys (``arch``): the same closed loop over
``RecoverableServer`` as ``jobs/serve.py``, counted in engine steps, with
its own server construction and probe.

The probe is one request alone at the traffic table's first prompt length
(past the window): its logits at the last prompt position and the next
``PROBE_DECODE_ROWS`` decode positions against the plain reference's full
forward, relative L2, logits and never tokens. Routing is discrete, so the
probe also hands the reference the experts the engine chose at every
position (``DecoderCore.route_tap``); the reference consults them only
where its own margin is under its ``TIE_EPS``, counts those rows, and any
other disagreement fails the run.
"""
from __future__ import annotations

import importlib
import os
import tempfile
import time

import numpy as np

from benchmark import metrics, xplane
from benchmark.jobs.serve import ClosedLoop

# ||engine - ref|| / ||ref|| per probed position. The engine stores weights
# in bfloat16, hands bfloat16 between its products (float32 accumulation),
# keeps K/V in a bfloat16 pool and lets Mosaic's float32 dot run as one
# bfloat16 pass; the reference takes the same (rounded) weights and does
# everything in float32. The limit lies between two readings on the chip
# (PERF.md, Findings, PR 28; tools/probe_readings.py prints both): the engine
# gives 5.8e-3 to 6.7e-3 over four seeds and five positions; the reference
# itself, its matrices first rounded to 3 mantissa bits (a scaled float8
# e4m3, the nearest precision below bfloat16's 7), gives 8.2e-2 to 9.0e-2
# against itself unrounded, and routes 2 798 of 24 592 rows elsewhere. 2e-2
# is three times the first and a quarter of the second. A dropped window,
# RoPE on the full layer or a capacity drop read tens of percent at the tiny
# size (tests/test_afmoe_serving.py).
LOGITS_TOL = 2e-2
PROBE_DECODE_ROWS = 4
# keys of the configuration file that the server's spec takes as they are
MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "sliding_window", "intermediate_size",
              "num_experts_per_tok", "num_shared_experts",
              "moe_intermediate_size", "route_norm", "route_scale",
              "rope_theta", "rms_norm_eps", "mup_enabled")


def layer_types(config: dict) -> list:
    """The types of the layers run here: the published list at the
    published indices ``layers_run`` keeps."""
    return [config["layer_types"][i] for i in config["layers_run"]]


def server_spec(config: dict, seed: int, workdir: str) -> dict:
    held = config["num_experts"]          # the experts this chip holds
    spec = dict(config["engine"])
    spec.update({k: config[k] for k in MODEL_KEYS})
    spec.update(
        arch=config["model_type"], layer_types=layer_types(config),
        num_dense_layers=config["num_dense_layers"],
        num_experts=config["deployment_cut"]["num_experts_published"],
        experts_held=held,
        expert_offset=config["deployment_cut"]["expert_offset"],
        vocab_size=config["vocab_size"],
        weight_dtype=config["weight_dtype"],
        model_seed=seed % 2**31, embed_seed=(seed + 1234) % 2**31,
        journal_path=os.path.join(workdir, "journal.wal"),
        snapshot_path=os.path.join(workdir, "snapshot.bin"))
    return spec


def build_server(config: dict, seed: int, workdir: str):
    # a program without the config-driven core (the parent of the PR that
    # brought it) would build its default block from a spec it does not
    # understand: fail here, at once, with an ImportError
    from paddle_tpu.inference import decoder  # noqa: F401
    from paddle_tpu.inference.router import build_server_from_spec
    return build_server_from_spec(server_spec(config, seed, workdir))


class Loop(ClosedLoop):
    """The closed loop, which also keeps each decode row's context length:
    the windowed roofline needs them row by row."""

    def step(self) -> metrics.Step:
        # a request that already has a token decodes in this step, over its
        # prompt, what it has generated and the token it is fed
        lens = [req.prompt_len + self.seen[rid]
                for rid, (_, req) in self.live.items() if self.seen[rid]]
        rec = super().step()
        rec.decode_lens = lens if rec.decode_rows == len(lens) else None
        return rec


def take_routes(routes: dict, tap, slot, decoding: bool) -> None:
    """File one step's tap entries, (layer, view, positions [B, L], chosen
    [B * L, k]) of every expert layer call, under {layer: {position:
    chosen}}. The probe's request is the only live one: every prompt
    chunk is its own, and of the rows a decode step carries (one a slot)
    only its slot's is real, and only once it has a token."""
    for layer, view, positions, idx in tap:
        pos = np.asarray(positions).reshape(-1)
        got = np.asarray(idx)
        layout = getattr(view, "_layout", None)
        if layout is not None:                       # a packed step
            rows = [r for seg in layout.segs if seg[0] == "prefill"
                    for r in range(seg[1], seg[2])]
            rows += [seg[1] + slot for seg in layout.segs
                     if seg[0] == "decode" and decoding]
        elif hasattr(view, "_slot"):                 # a prompt chunk alone
            rows = range(pos.shape[0])
        else:                                        # decode rows alone
            rows = [slot] if decoding else []
        for r in rows:
            routes.setdefault(layer, {})[int(pos[r])] = got[r]


def probe_engine(server, config: dict, traffic: dict, seed: int) -> dict:
    """Serve the probe's request alone and return what the timed path
    produced: ``tokens`` (prompt plus the decode rows' inputs),
    ``prompt_len``, ``rows`` (the logits it sampled from, one a probed
    position) and ``routes`` {layer: [len(tokens), k]}, the experts the
    engine chose at every position."""
    tsm = server.engine.target
    core = tsm.core
    prompt_len = traffic["table"][0][0]
    rng = np.random.default_rng([seed, 2**31 - 1])
    prompt = rng.integers(0, config["vocab_size"], size=prompt_len).tolist()

    tap, inner = [], tsm.logits

    def tapped(hidden):
        out = inner(hidden)
        tap.append(out.data)
        return out
    tsm.logits = tapped
    core.route_tap = []
    rows, routes = [], {}
    try:
        rid = server.submit(prompt)
        for _ in range(8 + prompt_len // 8):
            before = len(server.generated(rid))
            del tap[:]
            del core.route_tap[:]
            server.step()
            slot = server.engine._by_rid[rid].slot
            take_routes(routes, core.route_tap, slot, before > 0)
            if len(server.generated(rid)) == before:
                continue
            if before == 0:    # admitted: the only [1, vocab] readout
                rows.append(np.asarray([a for a in tap if a.ndim == 2][-1][0]))
            else:
                rows.append(np.asarray(
                    [a for a in tap if a.ndim == 3][-1][slot, 0]))
            if len(rows) == 1 + PROBE_DECODE_ROWS:
                break
        gen = server.generated(rid)
        server.release(rid)
        server.drain_outcomes()
    finally:
        tsm.logits = inner
        core.route_tap = None
    if len(rows) != 1 + PROBE_DECODE_ROWS:
        raise AssertionError(f"captured {len(rows)} of the probe's "
                             f"{1 + PROBE_DECODE_ROWS} logit rows")
    for i, got in enumerate(rows):
        if int(got.argmax()) != gen[i]:
            raise AssertionError(f"probe position {i}: captured logits are "
                                 f"not the row the token was sampled from")
    tokens = prompt + gen[:PROBE_DECODE_ROWS]
    if any(sorted(by_pos) != list(range(len(tokens)))
           for by_pos in routes.values()):
        raise AssertionError("the engine's routing tap missed positions")
    return {"tokens": tokens, "prompt_len": prompt_len, "rows": rows,
            "routes": {layer: np.stack([by_pos[i]
                                        for i in range(len(tokens))])
                       for layer, by_pos in routes.items()}}


def probed_positions(probe: dict) -> list:
    """The positions of the probe's rows: the prompt's last and the decode
    rows after it."""
    return [probe["prompt_len"] - 1 + i for i in range(len(probe["rows"]))]


def compare_probe(tsm, config: dict, probe: dict, tol: float = LOGITS_TOL,
                  stats: dict | None = None, **reference_kw) -> float:
    """The probe's rows against the plain reference's full forward: worst
    relative L2 error; raises when it passes ``tol`` or the routing
    disagrees outside the reference's margin."""
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    stats = {} if stats is None else stats
    want = ref.logits(ref.weights_of(tsm), probe["tokens"],
                      rows=probed_positions(probe),
                      engine_routes=probe["routes"], stats=stats,
                      **reference_kw)
    errs = [float(np.linalg.norm(got - r) / np.linalg.norm(r))
            for got, r in zip(probe["rows"], want)]
    stats["rel_l2"] = errs
    if stats.get("route_flips_outside_margin"):
        raise AssertionError(
            f"engine and reference chose other experts outside the margin "
            f"in {stats['route_flips_outside_margin']} of "
            f"{stats['route_rows']} routed rows (rel. L2 {errs})")
    if not max(errs) <= tol:
        raise AssertionError(f"engine logits against the reference: relative "
                             f"L2 {errs} passes {tol}")
    return max(errs)


def check_probe(server, config: dict, traffic: dict, seed: int,
                tol: float = LOGITS_TOL, stats: dict | None = None) -> float:
    return compare_probe(server.engine.target, config,
                         probe_engine(server, config, traffic, seed), tol,
                         stats)


def moe_delta(after: dict, before: dict) -> dict:
    """The expert layer's counters over a stretch of steps, by kind."""
    keys = ("calls", "rows", "layer_calls", "rows_routed_here", "experts_hit")
    return {kind: {k: after[kind][k] - before[kind][k] for k in keys}
            for kind in ("decode", "mixed")}


def window_gauge(collector) -> tuple:
    """(pages in context, pages behind the window) summed over the
    ``paged_attn`` gauge samples of a profile session."""
    total = behind = 0
    for ev in (collector.events if collector is not None else ()):
        args = ev.get("args") or {}
        if ev.get("ph") == "C" and ev.get("name") == "paged_attn" and \
                "pages_in_context" in args:
            total += args["pages_in_context"]
            behind += args["pages_behind_window"]
    return total, behind


def device_memory_gb() -> tuple:
    """(GB in use now, highest so far) on the first device; zeros where the
    backend keeps no such figures."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (stats.get("bytes_in_use", 0) / 1e9,
            stats.get("peak_bytes_in_use", 0) / 1e9)


def run(config: dict, traffic: dict, *, seed: int, seconds: float,
        chips: int = 1, tracer=None, log=print, on_open=None,
        logits_tol: float = LOGITS_TOL) -> dict:
    """Build, check, warm up for the traffic file's ``warmup_steps``, then
    measure for ``seconds``. ``tracer`` (traced runs) profiles the first part
    of the window; the expert layer's counters are scraped when it starts
    and when it stops."""
    tracer = tracer or xplane.NoTracer()
    with tempfile.TemporaryDirectory(prefix="bench_serve_") as workdir:
        server = build_server(config, seed, workdir)
        core = server.engine.target.core
        try:
            faults, err, stats = [], float("nan"), {}
            try:
                err = check_probe(server, config, traffic, seed, logits_tol,
                                  stats=stats)
            except AssertionError as e:      # reported as correct: false
                faults.append(str(e))
            log(f"[serve_arch] probe logits against the reference: rel. L2 "
                f"{err:.2e} {stats}; device memory in use %.2f GB, highest "
                f"so far %.2f GB" % device_memory_gb())
            loop = Loop(server, traffic, config["vocab_size"], seed,
                        span=tracer.span)
            for _ in range(traffic["warmup_steps"]):
                loop.step()
            warm = len(loop.steps)
            log(f"[serve_arch] warm-up: {warm} steps; device memory in use "
                f"%.2f GB, highest so far %.2f GB" % device_memory_gb())
            if on_open:
                on_open()
            t_open = time.perf_counter()
            moe_open = core.moe_metrics()
            tracer.start()
            moe_traced = None

            def tick(last=False):
                # the counters of the traced steps: scraped once, at the
                # tick that stopped the profile
                nonlocal moe_traced
                tracer.tick(len(loop.steps) - warm, last=last)
                if moe_traced is None and \
                        getattr(tracer, "steps", None) is not None:
                    moe_traced = moe_delta(core.moe_metrics(), moe_open)
            while time.perf_counter() - t_open < seconds:
                loop.step()
                tick()
            tick(last=True)
            faults += loop.audit()
            moe_window = moe_delta(core.moe_metrics(), moe_open)
        finally:
            server.close()
    win = loop.steps[warm:]
    e2e = metrics.serve_metrics(win, loop.requests, t_open)
    e2e.pop("ttft_p50_ms", None)     # two modes (short, long): not reported
    started = [r for r in loop.requests
               if r.t_submit > t_open or
               (r.token_times and r.token_times[-1] > t_open)]
    log(f"[serve_arch] window: {e2e.pop('_samples')}; experts {moe_window}")
    for f in faults:
        log(f"[serve_arch] FAULT {f}")
    counters = {"steps": len(win), "probe_rel_l2": err,
                "probe_route_ties_taken": stats.get("route_ties_taken"),
                "pool_blocks": config["engine"]["num_blocks"],
                "moe_window": moe_window}
    mixed = moe_window["mixed"]
    if mixed["layer_calls"]:
        counters["expert_rows_per_step"] = mixed["rows_routed_here"] / (
            mixed["layer_calls"] * config["num_experts"])
    if moe_traced is not None:
        from paddle_tpu.inference import telemetry
        counters["moe_traced"] = moe_traced
        total, behind = window_gauge(telemetry.last_session_collector())
        if total:
            counters["window_pages_skipped_share"] = 100.0 * behind / total
    return {
        "e2e": e2e,
        "t_open": t_open,
        "correct": not faults,
        "attempted": len(started),
        "failed": len(loop.unfinished()),
        "series": {
            "rows_per_step": [s.decode_rows + s.prefill_tokens for s in win],
            "decode_step_ms": [s.ms for s in win if not s.prefill_tokens],
            "mixed_step_ms": [s.ms for s in win if s.prefill_tokens],
            "blocks_live": [s.blocks_live for s in win],
            # every gap between two tokens of one request that ended in
            # the window: what ``itl_p95_ms`` is the 95th percentile of
            "token_gap_ms": [(b - a) * 1e3 for r in loop.requests
                             for a, b in zip(r.token_times,
                                             r.token_times[1:])
                             if b > t_open],
        },
        "counters": counters,
        "steps": win,
    }
