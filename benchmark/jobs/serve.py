"""Serving job: a closed loop over ``RecoverableServer``, counted in engine
steps.

The traffic file is a table of (prompt length, output length) pairs and a
number of clients. Client *i* sends entries *i*, *i* + clients, ... of the
table, cyclically, each one as soon as its last one has all its tokens: after
every ``server.step()`` the loop releases what finished and submits what
follows, before the next step. So the sequence of batches is a fact of the
table and the scheduler, the same in every run; ``--seed`` draws the weights
and the token ids and nothing else, and only the duration of each step is
measured.
"""
from __future__ import annotations

import contextlib
import math
import os
import tempfile
import time

import numpy as np

from benchmark import metrics, xplane

# Engine logits against the float32 reference run at "highest" precision, as
# ||engine - ref|| / ||ref|| per position. The engine multiplies float32 at the
# TPU's default precision (one bf16 pass, 2^-9 per product) and keeps K/V in a
# bfloat16 pool: a few 1e-3 through 4 layers (PR 21 measured 3-4e-3). A wrong
# page, position or mask replaces a whole attention output: tens of percent.
LOGITS_TOL = 3e-2
PROBE_DECODE_ROWS = 4


def build_server(config: dict, seed: int, workdir: str):
    """The server both of the program's transports build, from the
    configuration file's sizes; weights from ``seed``."""
    from paddle_tpu.inference.router import build_server_from_spec
    spec = dict(config["engine"])
    spec.update(
        d_model=config["d_model"], heads=config["n_heads"], ffn=config["d_ff"],
        layers=config["n_layers"], vocab=config["n_vocab"],
        model_seed=seed % 2**31, embed_seed=(seed + 1234) % 2**31,
        # read out against the embedding rolled one row, so that greedy
        # streams walk the vocabulary (see build_model_from_spec)
        head_roll=1,
        journal_path=os.path.join(workdir, "journal.wal"),
        snapshot_path=os.path.join(workdir, "snapshot.bin"))
    return build_server_from_spec(spec)


def client_schedule(traffic: dict, client: int, turn: int) -> tuple[int, int]:
    """(prompt length, output length) of ``client``'s ``turn``-th request."""
    table, n = traffic["table"], traffic["clients"]
    mine = table[client::n]
    prompt, out = mine[turn % len(mine)]
    if turn == 0 and traffic.get("stagger_first_output"):
        # start the clients out of step with each other
        out = math.ceil(out * (client + 1) / n)
    return prompt, out


class ClosedLoop:
    """Plays a traffic table against a server, one engine step per call."""

    def __init__(self, server, traffic: dict, vocab: int, seed: int,
                 clock=time.perf_counter, span=contextlib.nullcontext):
        self.server = server
        self.traffic = traffic
        self.vocab = vocab
        self.seed = seed
        self.clock = clock
        self.span = span                           # span(name): a context
        self.eng = server.engine.engine            # PagedServingEngine
        self.turn = [0] * traffic["clients"]       # next turn of each client
        self.live = {}                             # rid -> (client, Request)
        self.seen = {}                             # rid -> tokens seen so far
        self.requests = []                         # every Request, in order
        self.steps = []                            # every Step, in order
        self.prompts = {}                          # rid -> ids (kept for tests)
        self.block_size = self.eng.cache.block_size
        self._idle = set(range(traffic["clients"]))   # clients with nothing out
        self._status = {}                          # rid -> terminal outcome
        self._prefilled = self._prefill_counter()

    def _prefill_counter(self) -> int:
        # prompt tokens the engine has advanced: computed plus adopted from
        # the prefix cache (checked against what was submitted, see audit())
        return (self.eng.prefill_stats.prefill_tokens
                + self.eng.prefix_stats.tokens_skipped)

    def _submit(self, client: int) -> None:
        turn = self.turn[client]
        self.turn[client] += 1
        prompt_len, out_len = client_schedule(self.traffic, client, turn)
        # each request's ids from their own stream: no two prompts share a
        # prefix except by chance
        rng = np.random.default_rng([self.seed, client, turn])
        ids = rng.integers(0, self.vocab, size=prompt_len).tolist()
        req = metrics.Request(prompt_len, out_len, self.clock())
        rid = self.server.submit(ids)
        self.live[rid] = (client, req)
        self.seen[rid] = 0
        self.requests.append(req)
        self.prompts[rid] = ids

    def step(self) -> metrics.Step:
        with self.span("bench.submit"):
            for client in sorted(self._idle):
                self._submit(client)
            self._idle.clear()
        t0 = self.clock()
        with self.span("bench.step"):
            self.server.step()       # reads the sampled tokens back: a sync
        t1 = self.clock()
        with self.span("bench.collect"):
            decode_rows = decode_pages = emitted = 0
            for rid, (client, req) in list(self.live.items()):
                n = len(self.server.generated(rid))
                new = n - self.seen[rid]
                if new:
                    if self.seen[rid]:
                        # a decode row attends over its prompt, what it has
                        # generated and the token it was fed
                        decode_rows += 1
                        decode_pages += -(-(req.prompt_len + self.seen[rid])
                                          // self.block_size)
                    emitted += new
                    req.token_times.extend([t1] * new)
                    self.seen[rid] = n
                if n >= req.out_len:
                    self.server.release(rid)
                    del self.live[rid]
                    self._idle.add(client)
            for oc in self.server.drain_outcomes():
                self._status[oc.rid] = oc.status
            prefilled = self._prefill_counter()
            occ = self.eng.cache.pool_occupancy(tiers_only=True)
            rec = metrics.Step(t0, t1, decode_rows,
                               prefilled - self._prefilled, emitted,
                               occ["active"], decode_pages)
            self._prefilled = prefilled
            self.steps.append(rec)
        return rec

    def unfinished(self) -> dict:
        """rid -> outcome of every request that ended in any way but
        FINISHED."""
        from paddle_tpu.inference.resilience import RequestOutcome
        return {rid: st for rid, st in self._status.items()
                if st != RequestOutcome.FINISHED}

    def audit(self) -> list[str]:
        """What the loop can hold the program's counters and outcomes to."""
        faults = []
        done = [r for r in self.requests if r.token_times]
        credited = sum(s.prefill_tokens for s in self.steps)
        lo = sum(r.prompt_len for r in done)
        hi = sum(r.prompt_len for r in self.requests)
        if not lo <= credited <= hi:
            faults.append(f"prefill counters credited {credited} prompt "
                          f"tokens; submitted prompts bound it to {lo}..{hi}")
        if self.unfinished():
            faults.append(f"requests not FINISHED: {self.unfinished()}")
        return faults


def check_probe(server, config: dict, traffic: dict, seed: int,
                tol: float = LOGITS_TOL) -> float:
    """One request alone, at the table's first prompt length: its logits at
    the last prompt position and the next ``PROBE_DECODE_ROWS`` positions
    against the plain reference's full forward. Returns the worst relative L2
    error; raises when it passes ``tol``."""
    import importlib
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    tsm = server.engine.target
    prompt_len = traffic["table"][0][0]
    rng = np.random.default_rng([seed, 2**31 - 1])
    prompt = rng.integers(0, config["n_vocab"], size=prompt_len).tolist()

    tap, inner = [], tsm.logits

    def tapped(hidden):
        out = inner(hidden)
        tap.append(out.data)
        return out
    tsm.logits = tapped
    rows = []
    try:
        rid = server.submit(prompt)
        for _ in range(8 + prompt_len // 8):
            before = len(server.generated(rid))
            del tap[:]
            server.step()
            if len(server.generated(rid)) == before:
                continue
            if before == 0:    # admitted: the only [1, vocab] readout
                rows.append(np.asarray([a for a in tap if a.ndim == 2][-1][0]))
            else:
                slot = server.engine._by_rid[rid].slot
                rows.append(np.asarray(
                    [a for a in tap if a.ndim == 3][-1][slot, 0]))
            if len(rows) == 1 + PROBE_DECODE_ROWS:
                break
        gen = server.generated(rid)
        server.release(rid)
        server.drain_outcomes()
    finally:
        tsm.logits = inner
    if len(rows) != 1 + PROBE_DECODE_ROWS:
        raise AssertionError(f"captured {len(rows)} of the probe's "
                             f"{1 + PROBE_DECODE_ROWS} logit rows")
    want = ref.logits(ref.weights_of(tsm), prompt + gen[:PROBE_DECODE_ROWS],
                      heads=config["n_heads"])
    errs = []
    for i, got in enumerate(rows):
        r = want[prompt_len - 1 + i]
        if int(got.argmax()) != gen[i]:
            raise AssertionError(f"probe position {i}: captured logits are "
                                 f"not the row the token was sampled from")
        errs.append(float(np.linalg.norm(got - r) / np.linalg.norm(r)))
    if not max(errs) <= tol:
        raise AssertionError(f"engine logits against the reference: relative "
                             f"L2 {errs} passes {tol}")
    return max(errs)


def run(config: dict, traffic: dict, *, seed: int, seconds: float,
        chips: int = 1, tracer=None, log=print, on_open=None,
        logits_tol: float = LOGITS_TOL) -> dict:
    """Build, check, warm up for the traffic file's ``warmup_steps``, then
    measure for ``seconds``. ``tracer`` (traced runs) profiles the first part
    of the window."""
    tracer = tracer or xplane.NoTracer()
    with tempfile.TemporaryDirectory(prefix="bench_serve_") as workdir:
        server = build_server(config, seed, workdir)
        try:
            faults, err = [], float("nan")
            try:
                err = check_probe(server, config, traffic, seed, logits_tol)
            except AssertionError as e:      # reported as correct: false
                faults.append(str(e))
            log(f"[serve] probe logits against the reference: rel. L2 {err:.2e}")
            loop = ClosedLoop(server, traffic, config["n_vocab"], seed,
                              span=tracer.span)
            for _ in range(traffic["warmup_steps"]):
                loop.step()
            warm = len(loop.steps)
            log(f"[serve] warm-up: {warm} steps")
            if on_open:
                on_open()
            t_open = time.perf_counter()
            tracer.start()
            while time.perf_counter() - t_open < seconds:
                loop.step()
                tracer.tick(len(loop.steps) - warm)
            tracer.tick(len(loop.steps) - warm, last=True)
            faults += loop.audit()
        finally:
            server.close()
    win = loop.steps[warm:]
    e2e = metrics.serve_metrics(win, loop.requests, t_open)
    started = [r for r in loop.requests
               if r.t_submit > t_open or
               (r.token_times and r.token_times[-1] > t_open)]
    log(f"[serve] window: {e2e.pop('_samples')}")
    for f in faults:
        log(f"[serve] FAULT {f}")
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
        },
        "counters": {"steps": len(win), "probe_rel_l2": err,
                     "pool_blocks": config["engine"]["num_blocks"]},
        "steps": win,
    }
