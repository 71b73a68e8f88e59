"""Serving telemetry subsystem (inference/telemetry.py + the
collector wiring in scheduler.py / speculative.py / recovery.py and
the BlockOOM.details satellite in paged_cache.py).

The acceptance bars:

* PASSIVE — token streams and terminal outcomes are BIT-IDENTICAL
  with a TraceCollector installed vs absent, across plain /
  prefix-cached / speculative / recoverable serving, including under
  a seeded fault storm (PR 5) and a crash/recover cycle (PR 6).
* ZERO OVERHEAD OFF — with no collector the engines perform zero
  clock reads (counting-clock test).
* RECOVERY-SAFE — engine snapshots carry no collector state; journal
  replay with tracing on neither diverges nor double-counts (replayed
  spans flagged, live-observed records frozen).
"""
import json
import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference import (BlockOOM, CrashInjector, EngineCrash,
                                  FaultInjector, MetricsRegistry,
                                  PagedKVCache, PagedServingEngine,
                                  RecoverableServer, SpeculativeEngine,
                                  StatsBase, TokenServingModel,
                                  TraceCollector)
from paddle_tpu.inference.telemetry import percentiles

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

pytestmark = pytest.mark.obs

D, HEADS, FFN, LAYERS = 32, 4, 64, 2
VOCAB = 50

_RNG = np.random.RandomState(1234)
_EMBED = _RNG.randn(VOCAB, D).astype(np.float32)


def _model():
    paddle.seed(0)
    return FusedMultiTransformer(D, HEADS, FFN, num_layers=LAYERS)


def _tsm():
    return TokenServingModel(_model(), _EMBED)


def _prompts(seed, n=4, lo=6, hi=10):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, VOCAB, int(L)))
            for L in rng.integers(lo, hi, n)]


def _drive(tsm, prompts, n_gen, *, collector=None, injector=None,
           max_iters=300, **eng_kw):
    """Token-ID serving loop over SpeculativeEngine (k=0 == plain
    paged decode). Returns (streams, outcome (rid, status) pairs,
    engine)."""
    kw = dict(k=0, max_batch=2, block_size=4, num_blocks=60,
              max_blocks_per_seq=10)
    kw.update(eng_kw)
    eng = SpeculativeEngine(tsm, None, collector=collector,
                            injector=injector, **kw)
    rids = [eng.submit(p) for p in prompts]
    done, failed, outcomes = {}, set(), []
    for _ in range(max_iters):
        live = [r for r in rids if r not in done and r not in failed]
        if not live:
            break
        eng.step()
        for oc in eng.outcomes:
            outcomes.append((oc.rid, oc.status, oc.step))
            if oc.failed:
                failed.add(oc.rid)
        eng.outcomes.clear()
        for r in live:
            if r in failed:
                continue
            if len(eng.generated(r)) >= n_gen:
                done[r] = eng.generated(r)[:n_gen]
                eng.release(r)
    else:
        raise AssertionError("telemetry driver did not converge")
    # drain the release outcomes too
    for oc in eng.outcomes:
        outcomes.append((oc.rid, oc.status, oc.step))
    eng.outcomes.clear()
    return done, outcomes, eng


# ---------------------------------------------------------------------
# satellite: the declarative stats base
# ---------------------------------------------------------------------

class TestStatsBase:
    def test_fields_derived_and_repr_are_generated(self):
        class Demo(StatsBase):
            __slots__ = FIELDS = ("hits", "misses")
            DERIVED = {"rate": 4}
            REPR = ("rate", "hits")

            @property
            def rate(self):
                total = self.hits + self.misses
                return self.hits / total if total else 0.0

        st = Demo()
        assert st.hits == 0 and st.misses == 0
        st.hits, st.misses = 2, 1
        assert st.as_dict() == {"hits": 2, "misses": 1,
                                "rate": round(2 / 3, 4)}
        assert repr(st) == "Demo(rate=0.6667, hits=2)"

    def test_every_declared_stat_is_export_visible(self):
        """The satellite guarantee: the five serving siblings export
        every slot AND every derived property through the generated
        as_dict — nothing can be added without becoming visible."""
        from paddle_tpu.inference import (PrefillStats,
                                          PrefixCacheStats,
                                          ResilienceStats,
                                          SpecDecodeStats, TenantStats)
        for cls in (PrefixCacheStats, PrefillStats, ResilienceStats,
                    TenantStats, SpecDecodeStats):
            st = cls()
            d = st.as_dict()
            for f in cls.FIELDS:
                assert f in d, f"{cls.__name__}.{f} not exported"
            for p in cls.DERIVED:
                assert p in d, f"{cls.__name__}.{p} not exported"
            assert tuple(cls.__slots__) == tuple(cls.FIELDS)
            assert repr(st).startswith(cls.__name__ + "(")

    def test_sibling_dicts_keep_their_keys(self):
        """Pre-refactor key sets survive (snapshots, benches and the
        doctor read them)."""
        from paddle_tpu.inference import PrefixCacheStats, TenantStats
        p = PrefixCacheStats()
        p.lookup_blocks, p.hit_blocks = 8, 6
        d = p.as_dict()
        assert d["hit_rate"] == 0.75 and d["blocks_saved"] == 6
        t = TenantStats()
        t.sheds, t.rejections = 1, 2
        assert t.as_dict()["failed"] == 3


# ---------------------------------------------------------------------
# the unified registry
# ---------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counters_gauges_histograms(self):
        reg = MetricsRegistry()
        reg.count("served")
        reg.count("served", 4)
        reg.gauge("depth", 7)
        for v in (1.0, 2.0, 3.0, 10.0):
            reg.observe("lat", v)
        d = reg.as_dict()
        assert d["served"] == 5 and d["depth"] == 7
        assert d["lat.count"] == 4 and d["lat.max"] == 10.0
        h = reg.histogram("lat")
        assert h["p50"] == 2.5 and h["count"] == 4
        assert reg.histogram("nope") == {"count": 0}

    def test_attach_stats_and_callable_flatten(self):
        from paddle_tpu.inference import ResilienceStats
        reg = MetricsRegistry()
        st = ResilienceStats()
        st.shed = 3
        reg.attach("resilience", st)
        reg.attach("tenants", lambda: {"a": {"queued": 2,
                                             "stats": {"sheds": 1}}})
        d = reg.as_dict()
        assert d["resilience.shed"] == 3          # live object
        st.shed = 4
        assert reg.as_dict()["resilience.shed"] == 4
        assert d["tenants.a.queued"] == 2
        assert d["tenants.a.stats.sheds"] == 1

    def test_delta_since_is_the_sampling_loop(self):
        reg = MetricsRegistry()
        reg.count("tok", 10)
        reg.gauge("cfg", "str-valued")            # non-numeric: skipped
        prev = reg.as_dict()
        reg.count("tok", 7)
        reg.count("fresh", 2)
        delta = reg.delta_since(prev)
        assert delta["tok"] == 7
        assert delta["fresh"] == 2                # absent before -> 0
        assert "cfg" not in delta

    def test_percentiles_helper(self):
        assert percentiles([]) == {"count": 0}
        assert percentiles([None, None]) == {"count": 0}
        p = percentiles([1.0, 3.0, None])
        assert p["count"] == 2 and p["p50"] == 2.0

    def test_engine_registry_unifies_the_stats_siblings(self):
        tsm = _tsm()
        col = TraceCollector()
        done, _, eng = _drive(tsm, _prompts(11, n=3), 6,
                              collector=col, k=0)
        d = eng.registry.as_dict()
        # the five siblings + tenant report + pool/queue gauges, one
        # flat namespace
        for key in ("prefix_cache.hit_rate", "prefill.decode_steps",
                    "resilience.shed", "spec.proposed",
                    "tenants.default.stats.tokens_served",
                    "pool.active", "pool.free", "queue.depth"):
            assert key in d, f"missing {key}"
        assert d["prefill.decode_steps"] > 0
        assert d["tenants.default.stats.tokens_served"] > 0
        # interval deltas: another request's worth of serving moves
        # only the moving parts
        prev = eng.registry.as_dict()
        rid = eng.submit(_prompts(12, n=1)[0])
        for _ in range(6):
            eng.step()
        delta = eng.registry.delta_since(prev)
        assert delta["prefill.decode_steps"] > 0
        assert delta["tenants.default.stats.tokens_served"] > 0
        # collector's own registry tracked the step/token counters
        cd = col.registry.as_dict()
        assert cd["steps.live"] == col.steps
        assert cd["tokens.decoded"] > 0
        assert cd["outcomes.finished"] == len(done)


# ---------------------------------------------------------------------
# satellite (PR 11): windowed-view edges — empty window, single mark,
# a window spanning the retention eviction
# ---------------------------------------------------------------------

class TestWindowedViewEdges:
    def test_empty_window(self):
        """Marks taken, nothing observed since: the interval view is
        an empty percentile dict, never a crash."""
        reg = MetricsRegistry()
        reg.observe("lat", 1.0)
        marks = reg.hist_marks()
        assert reg.values_since("lat", marks["lat"]) == []
        since = reg.percentiles_since(marks)
        assert since["lat"] == {"count": 0}
        # a registry with no histograms at all
        empty = MetricsRegistry()
        assert empty.hist_marks() == {}
        assert empty.percentiles_since() == {}
        assert empty.values_since("lat", 0) == []
        assert empty.last_value("lat") is None

    def test_single_mark_single_observation(self):
        reg = MetricsRegistry()
        marks = reg.hist_marks()            # before the series exists
        reg.observe("lat", 7.0)
        assert reg.hist_total("lat") == 1
        assert reg.last_value("lat") == 7.0
        vals = reg.values_since("lat", marks.get("lat", 0))
        assert vals == [7.0]
        since = reg.percentiles_since(marks)
        assert since["lat"]["count"] == 1
        assert since["lat"]["p50"] == 7.0 == since["lat"]["max"]

    def test_window_spanning_eviction(self):
        """A mark taken BEFORE the retention trim: the view clamps to
        what is retained (count < requested span), monotonic totals
        keep later marks exact."""
        reg = MetricsRegistry()
        reg.observe("lat", -1.0)
        marks = reg.hist_marks()            # mark at total=1
        n = 2 * reg.HIST_WINDOW             # fill to the trim edge...
        for i in range(n):
            reg.observe("lat", float(i))    # ...and over it
        assert reg.hist_total("lat") == n + 1
        vals = reg.values_since("lat", marks["lat"])
        # the trim dropped HIST_WINDOW observations, the window
        # clamps: retained = n + 1 - HIST_WINDOW
        assert len(vals) == n + 1 - reg.HIST_WINDOW
        assert vals[-1] == float(n - 1)
        since = reg.percentiles_since(marks)
        assert since["lat"]["count"] == len(vals)
        # a mark taken AFTER the trim stays exact
        m2 = reg.hist_marks()
        reg.observe("lat", 123.0)
        assert reg.values_since("lat", m2["lat"]) == [123.0]


# ---------------------------------------------------------------------
# satellite: structured BlockOOM
# ---------------------------------------------------------------------

class TestBlockOOMDetails:
    def test_alloc_oom_carries_pool_occupancy_dict(self):
        cache = PagedKVCache(LAYERS, HEADS, D // HEADS, 4, 6,
                             max_seqs=2, max_blocks_per_seq=4)
        cache.ensure(0, 12)            # 3 blocks
        cache.set_seq_tenant(1, "greedy")
        cache.ensure(1, 8)             # 2 blocks -> pool (5 usable) dry
        with pytest.raises(BlockOOM) as ei:
            cache.allocator.alloc(2)
        det = ei.value.details
        assert det["blocks_needed"] == 2 and det["blocks_free"] == 0
        assert det["active"] == 5 and det["usable"] == 5
        assert det["blocks_per_slot"] == {0: 3, 1: 2}
        assert det["blocks_per_tenant"] == {"greedy": 2}
        # the dict IS the message's source: they agree
        assert "blocks per slot: {0: 3, 1: 2}" in str(ei.value)
        assert det == dict(cache.pool_occupancy(), blocks_needed=2,
                           blocks_free=0)

    def test_injected_oom_is_flagged(self):
        inj = FaultInjector(oom_at=[1])
        inj.begin_step(1)
        with pytest.raises(BlockOOM) as ei:
            inj.on_alloc("target")
        assert ei.value.details == {"injected": True, "pool": "target",
                                    "step": 1}

    def test_shed_emits_the_occupancy_event(self):
        """Every shed/OOM surfaces the structured dict as a telemetry
        event: a whole-step forced OOM sheds one request and the
        collector holds both the ``block_oom`` instant (injected
        details) and the ``oom_shed`` occupancy dump."""
        tsm = _tsm()
        col = TraceCollector()
        # ALL allocs fail over a 4-step window: with 4-token blocks
        # every slot crosses a page boundary inside it, so at least
        # one growth hits the forced OOM and preemption cannot help
        inj = FaultInjector(oom_at=[3, 4, 5, 6])
        done, outcomes, eng = _drive(
            tsm, _prompts(21, n=3, lo=8, hi=12), 8, collector=col,
            injector=inj, k=0, num_blocks=9, max_blocks_per_seq=6,
            max_batch=2)
        assert any(s == "failed_oom" for _, s, _ in outcomes)
        names = [ev["name"] for ev in col.events if ev.get("ph") == "i"]
        assert "block_oom" in names and "oom_shed" in names
        shed_ev = next(ev for ev in col.events
                       if ev["name"] == "oom_shed")
        for key in ("active", "cached_free", "free", "usable",
                    "blocks_per_slot", "rid", "tenant", "step"):
            assert key in shed_ev["args"]
        oom_ev = next(ev for ev in col.events
                      if ev["name"] == "block_oom")
        assert oom_ev["args"]["injected"] is True
        assert col.registry.as_dict()["events.oom_shed"] >= 1


# ---------------------------------------------------------------------
# zero overhead when off: the counting-clock test (the CountingTime
# stand-in lives in conftest.py — shared with the monitor and cost
# suites via the ``counting_clock`` fixture)
# ---------------------------------------------------------------------

class TestZeroOverheadWhenOff:
    def _serve(self, collector):
        model = _model()
        eng = PagedServingEngine(model, max_batch=2, block_size=4,
                                 num_blocks=20, max_blocks_per_seq=5,
                                 collector=collector)
        rng = np.random.RandomState(3)
        for _ in range(2):
            eng.submit(paddle.to_tensor(
                rng.randn(6, D).astype(np.float32)))
        x = np.zeros((2, 1, D), np.float32)
        for _, slot, h in eng.admitted:
            x[slot, 0] = np.asarray(h.numpy())[0]
        eng.admitted.clear()
        for _ in range(4):
            out = eng.step(paddle.to_tensor(x))
            x = np.asarray(out.numpy())[:, :1].copy()
        eng.release(0)
        return eng

    def test_no_collector_means_zero_clock_reads(self, counting_clock):
        """The acceptance clause: with no collector installed the
        serving hot path performs NO clock reads — submit, prefill,
        steps, release. (Deadline-carrying submits still read the
        monotonic clock, as before this PR — that is behavioral
        state, not telemetry.)"""
        self._serve(collector=None)
        assert counting_clock.calls == 0

    def test_collector_reads_the_injected_clock_only(self,
                                                     counting_clock):
        """Sanity for the counter itself, and for clock injection: a
        collector built AFTER the patch reads only through the
        patched module / its injected clock."""
        self._serve(collector=TraceCollector())
        assert counting_clock.calls > 0

    def test_collector_reads_the_cpu_clock_where_it_reads_the_other(
            self):
        """Both clocks are injectable, and the second is read only
        where a span, a phase or a step opens or closes: never more
        often than the first, at most twice a span event."""
        reads = {"wall": 0, "cpu": 0}

        def clock(which):
            def read():
                reads[which] += 1
                return 0.0
            return read
        col = TraceCollector(clock=clock("wall"), cpu_clock=clock("cpu"))
        assert reads == {"wall": 1, "cpu": 0}       # construction
        self._serve(collector=col)
        spans = [ev for ev in col.events if ev["ph"] == "X"]
        assert spans and all(ev["args"]["cpu"] == 0.0 for ev in spans)
        assert 0 < reads["cpu"] <= 2 * len(spans)
        assert reads["cpu"] < reads["wall"]

    def test_deterministic_injected_clock(self):
        """A fake clock makes every latency exact: TTFT/TPOT/queue
        wait derive purely from the recorded stamps."""
        t = [0.0]

        def clock():
            t[0] += 1.0
            return t[0]

        col = TraceCollector(clock=clock)
        col.on_submit(0, "a", 5)       # t=2 (t=1 was construction)
        col.on_admitted(0, 0, retry=False)   # t=3
        col.on_first_token(0)          # t=4
        col.on_decode([0], 1)          # t=5
        col.on_decode([0], 1)          # t=6
        col.on_outcome(0, "finished", 2)
        rec = col.requests[0]
        assert rec.queue_wait_s == 1.0
        assert rec.ttft_s == 2.0
        assert rec.tpot_s == 2.0       # (6 - 4) / (2 - 1)
        s = col.request_summary()
        assert s["overall"]["requests"] == 1
        assert s["per_tenant"]["a"]["ttft_s"]["p50"] == 2.0


# ---------------------------------------------------------------------
# passivity: bit-identity with tracing on vs off, all four modes
# ---------------------------------------------------------------------

class TestPassiveBitIdentity:
    N_GEN = 8

    def _both(self, seed, **eng_kw):
        tsm = _tsm()
        prompts = _prompts(seed)
        base, base_oc, _ = _drive(tsm, prompts, self.N_GEN, **eng_kw)
        col = TraceCollector()
        traced, traced_oc, eng = _drive(tsm, prompts, self.N_GEN,
                                        collector=col, **eng_kw)
        assert traced == base, "tracing changed a token stream"
        assert traced_oc == base_oc, "tracing changed an outcome"
        return col, eng

    def test_plain_paged(self):
        col, eng = self._both(41, k=0)
        assert col.steps > 0 and len(col.requests) == 4
        assert all(r.outcome == "finished"
                   for r in col.requests.values())

    def test_prefix_cached(self):
        col, eng = self._both(42, k=0, prefix_cache=True)
        assert eng.engine.prefix_cache

    @pytest.mark.spec
    def test_speculative(self):
        col, eng = self._both(43, k=2)
        # spec rounds recorded their spans and rollback accounting
        names = {ev["name"] for ev in col.events}
        assert {"spec_round", "draft_roll", "sample_verify",
                "verify"} <= names
        # emitted tokens (rollback-adjusted) match the streams
        for rid, rec in col.requests.items():
            gen = len(eng.generated(rid)) if rid in eng._by_rid \
                else None
            if gen is not None:
                # tokens = consumed decode rows minus rejected; the
                # stream holds prompt-independent generated tokens
                # (first token comes from prefill, not a decode row)
                assert rec.tokens == gen - 1 or rec.tokens == gen

    @pytest.mark.faults
    def test_under_fault_storm(self):
        """PR 5 composition: a seeded storm (forced OOM sheds + NaN
        slots) with tracing on — same outcomes, same survivor
        streams, and the failures are visible in the trace."""
        kw = dict(k=0, num_blocks=16, max_blocks_per_seq=8,
                  max_batch=2)
        tsm = _tsm()
        prompts = _prompts(44, n=4, lo=8, hi=12)
        runs = {}
        for tag, col in (("off", None), ("on", TraceCollector())):
            inj = FaultInjector(oom_at=[4], nan_at={6: [1]})
            runs[tag] = _drive(tsm, prompts, self.N_GEN,
                               collector=col, injector=inj, **kw)
        base, base_oc, _ = runs["off"]
        traced, traced_oc, eng = runs["on"]
        assert traced == base and traced_oc == base_oc
        col = eng.collector
        statuses = {r.outcome for r in col.requests.values()}
        assert "failed_numeric" in statuses or \
            "failed_oom" in statuses
        # every terminal outcome in the engine is in the trace, once
        assert sorted((r.rid, r.outcome)
                      for r in col.requests.values()
                      if r.outcome is not None) == \
            sorted(set((rid, s) for rid, s, _ in traced_oc))


# ---------------------------------------------------------------------
# recovery safety: crash/recover with tracing on
# ---------------------------------------------------------------------

def _drive_recoverable(tsm, prompts, n_gen, jp, sp, injector,
                       collector, max_iters=300):
    eng = SpeculativeEngine(tsm, None, k=0, max_batch=2, block_size=4,
                            num_blocks=60, max_blocks_per_seq=10,
                            injector=injector, collector=collector)
    srv = RecoverableServer(eng, journal_path=jp, snapshot_path=sp,
                            snapshot_every=4)
    rids = [srv.submit(p) for p in prompts]
    done, failed = {}, set()
    restores = 0
    for _ in range(max_iters):
        live = [r for r in rids if r not in done and r not in failed]
        if not live:
            break
        try:
            srv.step()
            for oc in srv.drain_outcomes():
                if oc.failed:
                    failed.add(oc.rid)
            for r in live:
                if r in failed:
                    continue
                if len(srv.generated(r)) >= n_gen:
                    done[r] = srv.generated(r)[:n_gen]
                    srv.release(r)
        except EngineCrash:
            srv = RecoverableServer.recover(
                tsm, None, journal_path=jp, snapshot_path=sp,
                injector=injector, collector=collector)
            srv.check_invariants()
            restores += 1
    else:
        raise AssertionError("recoverable driver did not converge")
    srv.close()
    return done, restores, srv


class TestRecoverySafety:
    N_GEN = 8

    @pytest.mark.recovery
    def test_crash_recover_cycle_is_traced_not_diverged(self, tmp_path):
        """PR 6 composition: an injected crash + snapshot/replay
        recovery with the collector riding through ``recover`` — the
        streams stay bit-identical to the no-collector crash run,
        replayed steps are FLAGGED, and no request's terminal outcome
        or latency is double-counted."""
        tsm = _tsm()
        prompts = _prompts(51)
        runs = {}
        for tag, col in (("off", None), ("on", TraceCollector())):
            # post_journal first: the round IS journaled but the death
            # lands before the caller sees it, so recovery must replay
            # real rounds (snapshot_every=4 keeps the snapshot behind)
            inj = CrashInjector(crash_at={3: "post_journal",
                                          6: "pre_journal"})
            jp = str(tmp_path / f"{tag}.wal")
            sp = str(tmp_path / f"{tag}.ckpt")
            runs[tag] = (*_drive_recoverable(
                tsm, prompts, self.N_GEN, jp, sp, inj, col), col, inj)
        base, base_restores, _, _, _ = runs["off"]
        traced, restores, srv, col, inj = runs["on"]
        assert inj.crashes == 2 and restores == 2
        assert traced == base, \
            "tracing changed streams across the crash storm"
        # replayed work is flagged, not double-counted
        assert col.replayed_steps > 0
        flagged = [ev for ev in col.events
                   if (ev.get("args") or {}).get("replay")]
        assert flagged, "replayed spans must carry the replay flag"
        # each request: exactly one terminal outcome in the trace
        finished = [r for r in col.requests.values()
                    if r.outcome is not None]
        assert len(finished) == len(prompts)
        assert col.registry.as_dict()["outcomes.finished"] == \
            len(prompts)
        # latency histograms saw each request at most once
        assert col.registry.histogram(
            "latency.ttft_s")["count"] <= len(prompts)
        # summary excludes nothing live (no replay-born requests here:
        # every rid was submitted before the first crash)
        assert col.request_summary()["overall"]["requests"] == \
            len(prompts)

    def test_snapshot_carries_no_collector_state(self):
        """Recovery-safe clause: wall-clock telemetry never enters
        engine-behavioral state — a traced engine's snapshot equals
        the untraced engine's snapshot, bit for bit."""
        import pickle
        tsm = _tsm()
        prompts = _prompts(52, n=2)
        snaps = {}
        for tag, col in (("off", None), ("on", TraceCollector())):
            eng = SpeculativeEngine(tsm, None, k=0, max_batch=2,
                                    block_size=4, num_blocks=30,
                                    max_blocks_per_seq=8,
                                    collector=col)
            for p in prompts:
                eng.submit(p)
            for _ in range(3):
                eng.step()
            snaps[tag] = pickle.dumps(eng.snapshot())
        assert snaps["on"] == snaps["off"]

    def test_restore_wires_the_callers_collector(self):
        tsm = _tsm()
        col = TraceCollector()
        eng = SpeculativeEngine(tsm, None, k=0, max_batch=2,
                                block_size=4, num_blocks=30,
                                max_blocks_per_seq=8)
        eng.submit(_prompts(53, n=1)[0])
        eng.step()
        restored = SpeculativeEngine.restore(tsm, None, eng.snapshot(),
                                             collector=col)
        assert restored.collector is col
        assert restored.engine.collector is col
        restored.step()
        assert col.steps > 0
        # the restored engine's registry re-attached the spec stats
        assert "spec.proposed" in restored.registry.as_dict()


# ---------------------------------------------------------------------
# the step timeline + request lifecycle detail
# ---------------------------------------------------------------------

class TestTimelineAndLifecycle:
    def test_step_phases_and_gauges(self):
        tsm = _tsm()
        col = TraceCollector()
        _drive(tsm, _prompts(61, n=3), 6, collector=col, k=0)
        phases = {}
        for ev in col.events:
            if ev.get("ph") == "X":
                phases[ev["name"]] = phases.get(ev["name"], 0) + 1
        # every step bracketed with its phases (the k=0 spec host
        # serves through step_multi, whose step kind is "verify")
        assert phases["verify"] == col.steps
        for name in ("model", "bookkeeping", "admission"):
            assert phases.get(name, 0) >= col.steps, \
                f"phase {name} missing from some step"
        # prefill ran as its own span (synchronous admission)
        assert phases.get("prefill", 0) >= 3
        # a healthy run tears nothing down: no span flagged aborted
        assert not any((ev.get("args") or {}).get("aborted")
                       for ev in col.events)
        # per-step gauges: pool tiers + queue depths + tenant charge
        gauges = [ev for ev in col.events if ev.get("ph") == "C"]
        tracks = {ev["name"] for ev in gauges}
        assert tracks == {"pool", "pool_write", "queue", "tenant_blocks",
                          "step_program"}
        # the CPU runs every model call per op, and the gauge says why
        calls = [ev["args"] for ev in gauges if ev["name"] == "step_program"]
        assert len(calls) >= col.steps
        assert all(a == {"captured": 0, "programs": 0, "no_kernel": 1}
                   for a in calls)
        pool = next(ev for ev in gauges if ev["name"] == "pool")
        assert {"active", "cached_free", "free"} <= set(pool["args"])
        # what each step's K/V appends moved: pages of the donated
        # pool, a small share of it, and at least a row a step
        writes = [ev["args"] for ev in gauges if ev["name"] == "pool_write"]
        assert all(w["rows_written"] > 0 and
                   0 < w["pool_bytes_written"] < w["pool_bytes"]
                   and w["pages_written"] > 0 for w in writes)
        # spans nest sanely: phases sit inside their step's interval
        steps = [(ev["ts"], ev["ts"] + ev["dur"]) for ev in col.events
                 if ev.get("ph") == "X" and ev["name"] == "verify"]
        for ev in col.events:
            if ev.get("ph") == "X" and ev["name"] == "model":
                t0, t1 = ev["ts"], ev["ts"] + ev["dur"]
                assert any(s0 - 1e-9 <= t0 and t1 <= s1 + 1e-9
                           for s0, s1 in steps), \
                    "model phase outside any step span"

    def test_chunked_prefill_and_preemption_lifecycle(self):
        """Token-budget (Sarathi) mode + a pool small enough to force
        preemption: the request records show prefill chunks, the
        preempted -> readmitted arc with a positive stall, and the
        'prefill' step phase."""
        model = _model()
        col = TraceCollector()
        eng = PagedServingEngine(model, max_batch=2, block_size=4,
                                 num_blocks=11, max_blocks_per_seq=8,
                                 chunk_tokens=4,
                                 prefill_token_budget=8,
                                 collector=col)
        rng = np.random.RandomState(5)
        for T in (16, 14):
            eng.submit(paddle.to_tensor(
                rng.randn(T, D).astype(np.float32)))
        x = np.zeros((2, 1, D), np.float32)
        for _ in range(80):
            if eng.num_active == 0 and eng.num_prefilling == 0 \
                    and not eng.queue:
                break       # both capacity-finished and auto-released
            out = eng.step(paddle.to_tensor(x))
            for _, slot, h in eng.admitted:
                x[slot, 0] = np.asarray(h.numpy())[0]
            eng.admitted.clear()
            if out is not None:
                x = np.asarray(out.numpy())[:, :1].copy()
        recs = list(col.requests.values())
        assert all(r.chunks > 0 for r in recs)
        chunk_events = [e for r in recs for e in r.events
                        if e[1] == "prefill_chunk"]
        assert chunk_events
        preempted = [r for r in recs if r.preemptions > 0]
        assert preempted, "workload failed to force a preemption"
        for r in preempted:
            names = [name for _, name, _ in r.events]
            assert "preempted" in names and "readmitted" in names
            assert names.index("preempted") < names.index("readmitted")
            assert r.stall_s > 0
        # the mixed-step prefill phase is on the timeline
        assert any(ev.get("ph") == "X" and ev["name"] == "prefill"
                   for ev in col.events)

    def test_paged_attn_gauge_rides_the_ragged_layout(self, tmp_path,
                                                       capsys):
        """Token-budget mode, packed steps: every launch samples the kernel's
        launch plan (grid steps, pages and heads a grid step) on the
        ``paged_attn`` gauge track, inside the ``grow`` span that
        builds the layout — host arithmetic over shapes — and the
        trace report prints the series. Without a budget there is no
        ragged layout and no such track (the test above)."""
        import importlib
        from tools import trace_report
        pa = importlib.import_module(
            "paddle_tpu.ops.pallas.paged_attention")
        col = TraceCollector()
        eng = PagedServingEngine(_model(), max_batch=2, block_size=4,
                                 num_blocks=60, max_blocks_per_seq=10,
                                 prefill_token_budget=8,
                                 ragged_step="force", collector=col)
        rng = np.random.RandomState(6)
        for T in (13, 11, 10):
            eng.submit(paddle.to_tensor(
                rng.randn(T, D).astype(np.float32)))
        x = np.zeros((2, 1, D), np.float32)
        for _ in range(12):
            out = eng.step(paddle.to_tensor(x))
            for _, slot, h in eng.admitted:
                x[slot, 0] = np.asarray(h.numpy())[0]
            eng.admitted.clear()
            if out is not None:
                x = np.asarray(out.numpy())[:, :1].copy()
        samples = [ev for ev in col.events
                   if ev.get("ph") == "C" and ev["name"] == "paged_attn"]
        layouts = [ev for ev in col.events if ev.get("ph") == "X"
                   and ev["name"] == "grow"
                   and (ev.get("args") or {}).get("what") == "layout"]
        assert samples and len(samples) == len(layouts)
        for ev in samples:
            a = ev["args"]
            assert set(a) == {"grid_steps", "live_steps",
                              "pages_per_step", "heads_per_step"}
            # what the launch walks, under the plan's bound
            assert 1 <= a["live_steps"] <= a["grid_steps"]
            # 10 table entries a sequence, HEADS kv heads, one shard
            assert a["heads_per_step"] == HEADS
            assert a["grid_steps"] % -(-10 // a["pages_per_step"]) == 0
        # a decode-only packed launch: 2 slots, one tile each
        plan = pa.launch_plan(2, HEADS, 1, 10, 4, D // HEADS, 4)
        assert plan.grid_steps in {ev["args"]["grid_steps"]
                                   for ev in samples}
        assert col.registry.gauges["paged_attn.grid_steps"] == \
            samples[-1]["args"]["grid_steps"]
        path = str(tmp_path / "g.trace.json")
        col.save_chrome_trace(path)
        assert trace_report.main([path]) == 0
        out = capsys.readouterr().out
        assert "paged_attn.grid_steps:" in out
        assert "paged_attn.pages_per_step:" in out
        assert "paged_attn.live_steps:" in out
        assert re.search(r"paged-attention launch: \d+ live grid step\(s\) "
                         r"walked of a bound of \d+ \(", out), out
        # the pool writes beside the pool's size
        assert "pool_write.pages_written:" in out
        assert re.search(r"pool writes: [\d.]+ page\(s\) and [\d.]+ "
                         r"row\(s\) a step over all layers, [\d.]+ MB "
                         r"of a [\d.]+ MB pool", out), out
        # the step program's gauge, a sample a model call: on the CPU
        # the kernel predicate is false and every call runs per op
        assert re.search(r"step program: 0 of \d+ model call\(s\) ran as "
                         r"one compiled program \(0\.0 %\), 0 program\(s\) "
                         r"compiled; per op because: no_kernel x\d+", out), out
        assert trace_report.main([path, "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["data"]["gauges"]["paged_attn.grid_steps"][
            "samples"] == \
            len(samples)

    @pytest.mark.spec
    def test_rollback_events_ride_the_spec_engine(self):
        """An adversarial draft (noise logits) forces rejections:
        rolled_back lifecycle events appear and token counts stay
        rollback-adjusted."""
        tsm = _tsm()
        col = TraceCollector()
        inj = FaultInjector(draft_nan_at={2: [0, 1], 3: [0, 1]})
        done, _, eng = _drive(tsm, _prompts(62, n=2), 6,
                              collector=col, injector=inj, k=2)
        rolled = [e for r in col.requests.values() for e in r.events
                  if e[1] == "rolled_back"]
        assert rolled and all(a["rejected"] > 0 for _, _, a in rolled)

    def test_unknown_rids_are_not_synthesized(self):
        """A collector wired onto a restored engine with in-flight
        requests it never saw submitted: lifecycle hooks for those
        rids are no-ops — no tenant-less half-records, no negative
        token tallies from rollbacks (a request is traced from its
        submit or not at all)."""
        col = TraceCollector()
        col.on_decode([7], 3)
        col.on_rollback(7, 2)
        col.on_admitted(7, 0, retry=False)
        col.on_outcome(7, "finished", 4)
        assert col.requests == {}
        s = col.request_summary()
        assert s["overall"]["requests"] == 0
        assert s["overall"]["tokens"] == 0
        assert None not in s["per_tenant"]

    def test_replay_flag_stays_off_counter_events(self):
        """During replay, gauge ('C') events must NOT gain a bogus
        'replay' series — their args IS the series->value map."""
        col = TraceCollector()
        col.set_replay(True)
        col.begin_step(1)
        col.end_step({"pool": {"active": 4}})
        col.on_event("marker")
        col.set_replay(False)
        counter = next(ev for ev in col.events if ev["ph"] == "C")
        assert counter["args"] == {"active": 4}
        span = next(ev for ev in col.events if ev["ph"] == "X")
        assert span["args"]["replay"] is True
        inst = next(ev for ev in col.events if ev["ph"] == "i")
        assert inst["args"]["replay"] is True

    def test_event_buffer_bound(self):
        col = TraceCollector(max_events=3)
        for i in range(10):
            col.on_event(f"e{i}")
        assert len(col.events) == 3 and col.dropped == 7
        assert col.as_dict()["dropped_events"] == 7

    def test_long_lived_memory_bounds(self):
        """A long-lived traced server stays bounded: terminal request
        records evict oldest-first past ``max_requests``, per-record
        event logs cap (keeping the terminal verdict), and latency
        histograms keep a window, not O(total requests)."""
        col = TraceCollector(max_requests=4)
        for rid in range(10):
            col.on_submit(rid, "t", 5)
            col.on_admitted(rid, 0, retry=False)
            col.on_first_token(rid)
            col.on_outcome(rid, "finished", rid)
        assert len(col.requests) == 4
        assert col.evicted_requests == 6
        # oldest terminal evicted first; newest survive
        assert sorted(col.requests) == [6, 7, 8, 9]
        # live records are never evicted
        col2 = TraceCollector(max_requests=2)
        for rid in range(4):
            col2.on_submit(rid, "t", 5)      # all live, no outcome
        assert len(col2.requests) == 4 and col2.evicted_requests == 0
        # per-record event log caps but keeps the terminal event
        col3 = TraceCollector()
        col3.on_submit(0, "t", 5)
        rec = col3.requests[0]
        for i in range(2 * col3.MAX_REQ_EVENTS):
            col3.on_prefill_chunk(0, i)
        assert len(rec.events) == col3.MAX_REQ_EVENTS
        col3.on_outcome(0, "finished", 1)
        assert len(rec.events) == col3.MAX_REQ_EVENTS
        assert rec.events[-1][1] == "finished"
        # histogram window
        reg = MetricsRegistry()
        for i in range(5 * reg.HIST_WINDOW):
            reg.observe("lat", float(i))
        assert len(reg._hists["lat"]) <= 2 * reg.HIST_WINDOW
        assert reg.histogram("lat")["max"] == 5 * reg.HIST_WINDOW - 1


# ---------------------------------------------------------------------
# chrome trace export + the offline doctor
# ---------------------------------------------------------------------

class TestChromeTraceAndReport:
    def _trace_file(self, tmp_path):
        tsm = _tsm()
        col = TraceCollector()
        _drive(tsm, _prompts(71, n=3), 6, collector=col, k=0)
        path = str(tmp_path / "serve.trace.json")
        n = col.save_chrome_trace(path)
        assert os.path.getsize(path) == n
        return path, col

    def test_trace_is_valid_trace_events_json(self, tmp_path):
        path, col = self._trace_file(tmp_path)
        with open(path) as f:
            trace = json.load(f)
        evs = trace["traceEvents"]
        assert isinstance(evs, list) and evs
        for ev in evs:
            assert "ph" in ev and "name" in ev
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
            if ev["ph"] != "M":
                assert "ts" in ev
        # both tracks present: engine timeline + request async events
        assert {ev.get("pid") for ev in evs if ev["ph"] != "M"} == \
            {1, 2}
        reqs = [ev for ev in evs if ev.get("cat") == "request"]
        assert {ev["ph"] for ev in reqs} == {"b", "n", "e"}
        # metadata carries the machine-readable side
        md = trace["metadata"]
        assert md["summary"]["overall"]["requests"] == 3
        assert str(0) in set(str(k) for k in md["requests"])

    def test_trace_report_exit_codes(self, tmp_path, capsys):
        from tools import trace_report
        path, _ = self._trace_file(tmp_path)
        # 0: clean — prints spans + percentiles
        assert trace_report.main([path]) == 0
        out = capsys.readouterr().out
        assert "valid trace_events JSON" in out
        assert "model" in out and "ttft_s" in out
        assert trace_report.main([path, "--requests"]) == 0
        out = capsys.readouterr().out
        assert "submitted" in out and "first_token" in out
        # 2: unreadable — not JSON / missing file
        bad = str(tmp_path / "not.json")
        with open(bad, "w") as f:
            f.write("{truncated")
        assert trace_report.main([bad]) == 2
        assert trace_report.main([str(tmp_path / "missing.json")]) == 2
        # 1: structurally invalid traces
        for blob in ({"notTraceEvents": []},
                     {"traceEvents": [{"ph": "X", "name": "x",
                                       "ts": 1.0, "dur": -5.0}]},
                     {"traceEvents": [{"ph": "X", "ts": 0.0}]}):
            p = str(tmp_path / "bad.json")
            with open(p, "w") as f:
                json.dump(blob, f)
            assert trace_report.main([p]) == 1, blob

    def test_report_validate_rejects_foreign_shapes(self):
        from tools import trace_report
        assert trace_report.validate({"traceEvents": "nope"})
        assert trace_report.validate({}) != []
        assert trace_report.validate(
            {"traceEvents": [{"ph": "X", "name": "a", "ts": 0,
                              "dur": 1}]}) == []


# ---------------------------------------------------------------------
# the profile session is the switch (RecoverableServer), spans on the
# profiler's clock, the ring, and idle gaps by program span
# ---------------------------------------------------------------------

ROUND_CHILDREN = {"spec_round", "draft_roll", "embed", "verify", "grow",
                  "prefill", "bookkeeping", "model", "admission",
                  "sample_verify", "device_wait", "journal", "snapshot",
                  "gc"}      # a long collection, wherever it fell
SUBMIT_CHILDREN = {"submit.journal", "submit.embed", "submit.hash",
                   "submit.admit"}


def _spec_server(tmp_path, name="s", **spec):
    from paddle_tpu.inference.router import build_server_from_spec
    base = dict(d_model=D, heads=HEADS, ffn=FFN, layers=LAYERS,
                vocab=VOCAB, head_roll=1, max_batch=3, block_size=4,
                num_blocks=80, max_blocks_per_seq=12,
                prefill_token_budget=8, snapshot_every=4,
                journal_path=str(tmp_path / f"{name}.wal"),
                snapshot_path=str(tmp_path / f"{name}.snap"))
    base.update(spec)
    return build_server_from_spec(base)


class _Profile:
    """A jax.profiler trace without the Python tracer (as the
    benchmark takes it)."""

    def __init__(self, outdir):
        self.outdir = str(outdir)

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.outdir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def xplane(self):
        import glob
        found = glob.glob(os.path.join(self.outdir, "**", "*.xplane.pb"),
                          recursive=True)
        assert len(found) == 1
        return found[0]


def _serve_closed_loop(srv, prompts, n_gen, rounds, on_round=None):
    """``len(prompts)`` clients; each submits its next prompt when its
    last has ``n_gen`` tokens. Returns {rid: tokens} and the drained
    (rid, status) pairs."""
    pending = list(prompts)
    live, streams, outcomes = [], {}, []
    for i in range(rounds):
        if on_round is not None:
            on_round(i)
        while pending and len(live) < 3:
            live.append(srv.submit(pending.pop(0)))
        srv.step()
        for rid in list(live):
            if len(srv.generated(rid)) >= n_gen:
                streams[rid] = srv.generated(rid)[:n_gen]
                srv.release(rid)
                live.remove(rid)
        outcomes += [(oc.rid, oc.status) for oc in srv.drain_outcomes()]
    return streams, outcomes


@pytest.fixture
def fresh_session(monkeypatch):
    """No last session on entry; whatever the test installs is gone
    on exit."""
    from paddle_tpu.inference import telemetry
    monkeypatch.setattr(telemetry, "_session", None)
    return telemetry


class TestProfileSessionSwitch:
    def test_no_session_no_collector_no_clock_reads(
            self, tmp_path, counting_clock, fresh_session):
        # (an engine snapshot reads the monotonic clock once, for the
        # wall-clock deadlines it rebases: behavioral state, and here
        # only snapshot 0 of the constructor)
        srv = _spec_server(tmp_path, snapshot_every=0)
        built = counting_clock.calls
        streams, _ = _serve_closed_loop(srv, _prompts(5, n=5), 4, 14)
        srv.close()
        assert len(streams) == 5
        assert counting_clock.calls == built <= 1
        assert srv.engine.collector is None
        assert srv._session_col is None
        assert fresh_session.last_session_collector() is None

    def test_session_records_the_round_and_the_submit(
            self, tmp_path, fresh_session):
        srv = _spec_server(tmp_path)
        prof = _Profile(tmp_path / "prof")

        def switch(i):
            if i == 3:               # rounds 1-3 were dark
                assert srv.engine.collector is None
                prof.__enter__()
        _serve_closed_loop(srv, _prompts(6, n=8, lo=9, hi=14), 4, 12,
                           on_round=switch)
        col = srv.engine.collector
        assert col is fresh_session.last_session_collector()
        prof.__exit__()
        srv.step()                   # first round top after the stop
        assert srv.engine.collector is None
        assert fresh_session.last_session_collector() is col
        srv.close()

        spans = [ev for ev in col.events if ev["ph"] == "X"]
        names = {ev["name"] for ev in spans}
        assert {"round", "submit"} | ROUND_CHILDREN | SUBMIT_CHILDREN \
            <= names
        rounds = [ev for ev in spans if ev["name"] == "round"]
        assert len(rounds) == 9 and col.steps == 9
        assert [ev["args"]["round"] for ev in rounds] == \
            list(range(srv.rounds - 9, srv.rounds))
        # every span of a round names a parent that is open around it,
        # and the spans with one parent tile it without overlap
        eps = 1e-9
        for top in rounds:
            r, t0, t1 = top["args"]["round"], top["ts"], \
                top["ts"] + top["dur"]
            inside = [ev for ev in spans if ev["args"].get("round") == r
                      and t0 - eps <= ev["ts"] and ev is not top
                      and ev["ts"] + ev["dur"] <= t1 + eps]
            assert {ev["name"] for ev in inside} >= \
                {"spec_round", "embed", "verify", "model", "grow",
                 "sample_verify", "device_wait", "journal"}
            by_parent = {}
            for ev in inside:
                assert ev["name"] in ROUND_CHILDREN
                by_parent.setdefault(ev["args"]["parent"], []).append(ev)
            assert set(by_parent) <= ROUND_CHILDREN | {"round"}
            for parent, kids in by_parent.items():
                kids.sort(key=lambda ev: ev["ts"])
                for a, b in zip(kids, kids[1:]):
                    assert a["ts"] + a["dur"] <= b["ts"] + eps, \
                        (parent, a["name"], b["name"])
            direct = sum(ev["dur"] for ev in by_parent["round"])
            assert 0 <= top["dur"] - direct <= top["dur"]
        # the submit and its four children, in order, inside it
        for top in (ev for ev in spans if ev["name"] == "submit"):
            kids = [ev for ev in spans
                    if ev["args"].get("parent") == "submit"
                    and ev["name"] != "gc"
                    and top["ts"] - eps <= ev["ts"]
                    and ev["ts"] + ev["dur"]
                    <= top["ts"] + top["dur"] + eps]
            assert [ev["name"] for ev in kids] == \
                ["submit.journal", "submit.embed", "submit.hash",
                 "submit.admit"]
            assert isinstance(top["args"]["rid"], int)
        # requests submitted before the session are not synthesized
        assert col.requests and min(col.requests) == 3
        assert all(rec.queue_wait_s is not None and rec.queue_wait_s >= 0
                   for rec in col.requests.values())

        # ... and the same spans are in the profile, on the host plane
        import jax
        data = jax.profiler.ProfileData.from_file(prof.xplane())
        host = next(p for p in data.planes if p.name == "/host:CPU")
        pt = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats)) for line in host.lines for e in line.events
              if e.name.startswith("pt.")]
        tops = [e for e in pt if e[0] == "pt.round"]
        assert len(tops) == 9
        assert [e[3]["round"] for e in tops] == \
            [ev["args"]["round"] for ev in rounds]
        for name in ("pt.model", "pt.device_wait"):
            kids = [e for e in pt if e[0] == name]
            assert len(kids) >= 9
            assert all(any(t[1] <= k[1] and k[2] <= t[2] for t in tops)
                       for k in kids), name
        # spans about one request carry its rid (the submit learns
        # its rid when it ends: host-side only)
        admits = [e for e in pt if e[0] == "pt.submit.admit"]
        assert admits and sorted(e[3]["rid"] for e in admits) == \
            sorted(col.requests)
        assert any(e[0] == "pt.submit" for e in pt)

    def test_session_spans_say_work_and_wait(self, tmp_path,
                                             fresh_session):
        """The session's collector reads the thread's CPU clock at
        every span, and the OS's counters at the round, the submit
        and the gather (tests/test_span_cpu.py holds each field)."""
        srv = _spec_server(tmp_path)
        with _Profile(tmp_path / "prof"):
            _serve_closed_loop(srv, _prompts(6, n=5, lo=9, hi=14), 4, 8)
        srv.step()
        srv.close()
        spans = [ev for ev in fresh_session.last_session_collector().events
                 if ev["ph"] == "X"]
        counted = {"round", "submit", "submit.embed"}
        assert counted <= {ev["name"] for ev in spans}
        for ev in spans:
            args = ev["args"]
            # the two clocks tick apart: a span too short for the CPU
            # clock's tick may read a hair over its duration
            assert 0 <= args["cpu"] <= ev["dur"] + 1e-3, ev
            assert ("faults" in args and "preempted" in args) == \
                (ev["name"] in counted), ev
            assert ("gc_n" in args) == (ev["name"] in ("round", "submit"))
        waits = [ev for ev in spans if ev["name"] == "device_wait"]
        assert waits and all("parent" in ev["args"] for ev in waits)

    def test_streams_are_bit_identical_with_a_session_in_mid_flight(
            self, tmp_path, fresh_session):
        prompts = _prompts(8, n=7, lo=7, hi=13)
        off = _spec_server(tmp_path, "off")
        want = _serve_closed_loop(off, prompts, 5, 20)
        off.close()

        on = _spec_server(tmp_path, "on")
        prof = _Profile(tmp_path / "prof")

        def switch(i):
            if i == 4:
                prof.__enter__()       # requests 0-2 are in flight
            if i == 13:
                prof.__exit__()        # ... and so are later ones here
        got = _serve_closed_loop(on, prompts, 5, 20, on_round=switch)
        on.close()
        assert got == want and len(want[0]) == 7
        col = fresh_session.last_session_collector()
        assert col is not None and on.engine.collector is None
        assert col.steps == 9
        # it met requests it never saw submitted, and ignored them
        assert min(col.requests) >= 3
        assert all(rec.tokens >= 0 for rec in col.requests.values())

    def test_a_passed_collector_is_never_removed(self, tmp_path,
                                                 fresh_session):
        mine = TraceCollector()
        eng = SpeculativeEngine(_tsm(), None, k=0, max_batch=2,
                                block_size=4, num_blocks=60,
                                max_blocks_per_seq=10, collector=mine)
        srv = RecoverableServer(eng, journal_path=str(tmp_path / "j"),
                                snapshot_path=str(tmp_path / "s"))
        srv.submit(_prompts(9, n=1)[0])
        srv.step()
        with _Profile(tmp_path / "prof"):
            srv.step()
        srv.step()
        srv.close()
        assert srv.engine.collector is mine
        assert fresh_session.last_session_collector() is None
        rounds = [ev for ev in mine.events if ev["name"] == "round"]
        assert [ev["args"]["round"] for ev in rounds] == [1, 2, 3]
        assert not any(ev["args"].get("partial") for ev in rounds)

    def test_a_round_the_trace_stopped_in_is_flagged(self, tmp_path,
                                                     fresh_session,
                                                     monkeypatch):
        srv = _spec_server(tmp_path)
        srv.submit(_prompts(10, n=1)[0])
        flags = iter([True, True, True, False])   # submit, round 1 top
        monkeypatch.setattr(fresh_session, "profile_recording",  # and
                            lambda: next(flags, False))   # end, round 2
        srv.step()
        srv.step()
        srv.step()
        srv.close()
        col = fresh_session.last_session_collector()
        rounds = [ev for ev in col.events if ev["name"] == "round"]
        assert [bool(ev["args"].get("partial")) for ev in rounds] == \
            [False, True]
        assert srv.engine.collector is None


class TestRingAndCompileEvents:
    def test_the_ring_keeps_the_newest_and_counts_the_overwritten(self):
        col = TraceCollector(max_events=4)
        for i in range(10):
            col.span_begin(f"s{i}")
            col.span_end()
        assert [ev["name"] for ev in col.events] == \
            ["s6", "s7", "s8", "s9"]
        assert col.dropped == 6
        assert col.as_dict()["timeline_events"] == 4
        assert col.chrome_trace()["metadata"]["dropped_events"] == 6

    def test_spans_record_parent_and_round(self):
        col = TraceCollector(cpu_clock=lambda: 0.0)
        col.round_no = 7
        col.span_begin("round")
        col.begin_step(3, "verify")
        col.phase("prefill")
        col.span_begin("grow", rid=5)
        col.span_end()
        col.phase("model")
        col.end_step()
        col.span_begin("journal")
        col.span_end()
        col.span_end()
        got = {ev["name"]: ev["args"] for ev in col.events}
        assert got["grow"] == {"rid": 5, "round": 7, "parent": "prefill",
                               "cpu": 0.0}
        assert got["model"]["parent"] == "verify"
        assert got["verify"]["parent"] == "round"
        assert got["journal"]["parent"] == "round"
        # (the outermost span also carries the collector's pauses)
        assert set(got["round"]) == {"round", "cpu", "gc", "gc_n"}
        # a bare engine's collector has no round and no parent on top
        bare = TraceCollector()
        bare.span_begin("spec_round")
        bare.span_end()
        assert not {"round", "parent"} & set(bare.events[0]["args"])

    def test_a_compile_is_an_instant_on_the_round_it_fell_in(self):
        import jax
        import jax.numpy as jnp
        idle, busy = TraceCollector(), TraceCollector()
        busy.round_no = 12
        x = jnp.ones(5)
        busy.span_begin("round")
        jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
        busy.span_end()
        jax.jit(lambda x: x * 5 + 2)(x).block_until_ready()
        got = [ev for ev in busy.events if ev["name"] == "compile"]
        assert len(got) == 1 and got[0]["ph"] == "i"
        assert got[0]["args"]["round"] == 12
        assert got[0]["args"]["seconds"] > 0
        assert not idle.events      # open nowhere: somebody else's


class TestIdleGapsBySpan:
    def test_a_gap_is_booked_on_the_innermost_span(self):
        from paddle_tpu.profiler import idle_gaps as ig
        spans = [("pt.round", 0, 100), ("pt.model", 10, 40),
                 ("pt.grow", 20, 25), ("pt.device_wait", 60, 90),
                 ("pt.round", 120, 200)]
        segs = ig.innermost_segments(spans)
        assert segs == [
            ("pt.round", 0, 10), ("pt.model", 10, 20),
            ("pt.grow", 20, 25), ("pt.model", 25, 40),
            ("pt.round", 40, 60), ("pt.device_wait", 60, 90),
            ("pt.round", 90, 100), ("pt.round", 120, 200)]
        got = list(ig.book_gaps([(22, 30), (95, 130)], segs))
        assert got == [("pt.grow", 22, 25), ("pt.model", 25, 30),
                       ("pt.round", 95, 100), (ig.NO_SPAN, 100, 120),
                       ("pt.round", 120, 130)]
        assert ig.merge_intervals([(3, 10), (0, 5), (20, 30)]) == \
            [[0, 10], [20, 30]]

    def test_a_planted_clock_offset_is_recovered(self):
        from paddle_tpu.profiler import idle_gaps as ig
        rng = np.random.default_rng(0)
        planted, busy, waits, t = 1_250_000.0, [], [], 0.0
        for _ in range(40):
            busy.append([t, t + 85e6])            # the round's programs
            # the read returns 30-60 us after the last op, on a host
            # clock that runs ``planted`` ns ahead of the device's
            waits.append(t + 85e6 + planted + rng.uniform(30e3, 60e3))
            # the next round's first upload, nearer to the read's end
            # than the end that released it: not the pair
            busy.append([t + 85e6 + planted + 200e3,
                         t + 85e6 + planted + 250e3])
            t += 96e6
        waits.append(t + 500e6)                   # an empty round
        off, n = ig.estimate_clock_offset(busy, waits)
        assert n == 40
        assert planted + 30e3 <= off <= planted + 60e3
        assert ig.estimate_clock_offset(busy, []) == (0.0, 0)


class TestTraceReportKnowsTheRound:
    def test_round_self_time_is_printed_and_old_traces_load(
            self, tmp_path, capsys):
        from tools import trace_report
        col = TraceCollector()
        col.round_no = 1
        col.span_begin("round")
        col.span_begin("embed")
        col.span_end()
        col.begin_step(1, "verify")
        col.phase("model")
        col.end_step()
        col.span_end()
        path = str(tmp_path / "new.json")
        col.save_chrome_trace(path)
        assert trace_report.main([path]) == 0
        out = capsys.readouterr().out
        line = next(ln for ln in out.splitlines()
                    if ln.strip().startswith("round: 1 x"))
        assert "self" in line
        rep = trace_report.machine_report(col.chrome_trace())
        r = rep["spans"]["round"]
        assert 0 <= r["self_s"] <= r["total_s"]
        assert rep["spans"]["embed"]["self_s"] == \
            rep["spans"]["embed"]["total_s"]
        # a trace written before spans named their parents
        old = {"traceEvents": [
            {"name": "model", "ph": "X", "ts": 0.0, "dur": 5.0,
             "args": {"step": 1}, "pid": 1, "tid": 0},
            {"name": "step", "ph": "X", "ts": 0.0, "dur": 9.0,
             "args": {"step": 1}, "pid": 1, "tid": 0}]}
        path = str(tmp_path / "old.json")
        with open(path, "w") as f:
            json.dump(old, f)
        assert trace_report.main([path]) == 0
        out = capsys.readouterr().out
        assert "model: 1 x" in out and ", self " not in out
        # one input, not both and not neither
        with pytest.raises(SystemExit):
            trace_report.main([])
        assert trace_report.main(
            ["--xplane", str(tmp_path / "missing.xplane.pb")]) == 2

    def test_what_the_hash_chain_read_is_printed(self, tmp_path, capsys):
        """``submit.hash`` ends with ``bytes`` and ``keyed``; the dump's
        registry carries the engine's ``prefix_cache.*`` counters: the
        report prints both under the submit spans."""
        from tools import trace_report
        col = TraceCollector()
        eng = SpeculativeEngine(_tsm(), None, k=0, max_batch=2,
                                block_size=4, num_blocks=40,
                                max_blocks_per_seq=8, prefix_cache=True,
                                prefill_token_budget=8, collector=col)
        for n in (9, 14):
            eng.submit(list(range(n)))
        rows = np.zeros((8, D), np.float32)        # a caller with rows
        eng.engine.submit(rows)
        ends = [ev for ev in col.chrome_trace()["traceEvents"]
                if ev["name"] == "submit.hash"]
        assert [(ev["args"]["bytes"], ev["args"]["keyed"])
                for ev in ends] == \
            [(4 * 8, "ids"), (4 * 12, "ids"), (rows.nbytes, "rows")]
        path = str(tmp_path / "hash.json")
        col.save_chrome_trace(path)
        assert trace_report.main([path]) == 0
        out = capsys.readouterr().out
        total = 4 * 20 + rows.nbytes
        assert (f"submit.hash read {total} B in 3 submit(s), "
                f"{total / 3:.0f} B a request; keyed by ids x2, rows x1"
                ) in out
        assert (f"prefix cache: hashed_bytes {total:g}, "
                f"row_keyed_blocks 2") in out
        rep = trace_report.machine_report(col.chrome_trace())
        assert rep["submit_hash"] == {
            "submits": 3, "bytes": total,
            "keyed": {"ids": 2, "rows": 1},
            "hashed_bytes": total, "row_keyed_blocks": 2}
