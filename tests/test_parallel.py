"""Fork-shared parallel decoding (scheduler branch groups +
speculative RNG lanes + grammar logit masks).

The acceptance bar is the LANE ORACLE: ``submit(prompt, n=N,
seed=S)`` prefills the prompt ONCE, COW-forks N branch slots over the
same prompt pages, and the N streams must be BIT-IDENTICAL to N
independent submits of the same prompt with
``seed=branch_lane_seed(S, i)`` — under plain, prefix-cached,
speculative (mid-stream rollback), int8-paged and recoverable
(crash mid-group) serving, with ``check_invariants`` (which audits
group refcounts and deep page fingerprints) holding throughout.
Greedy groups must equal the lone-submit stream exactly. On top of
the oracle: best-of-n races (losers CANCELLED, ``bestof_pruned``
waste), the ``fork_stream`` beam primitive, grammar masks whose
streams are provably in-language, one-charge-per-reference ledger
conservation, and the group telemetry surface.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference import (CostLedger, CrashInjector,
                                  EngineCrash, RecoverableServer,
                                  SpeculativeEngine, TokenServingModel,
                                  TraceCollector, branch_lane_seed,
                                  logit_mask_fn, register_logit_mask)
from paddle_tpu.inference.monitor import HealthMonitor

pytestmark = pytest.mark.parallel

D, HEADS, FFN, LAYERS = 32, 4, 64, 2
BS, MB = 16, 4            # 16-token pages, 4 pages/seq (64 tokens)
VOCAB = 50

_RNG = np.random.RandomState(1234)
_EMBED = _RNG.randn(VOCAB, D).astype(np.float32)
_HEAD = _RNG.randn(D, VOCAB).astype(np.float32)


def _target():
    paddle.seed(0)
    core = FusedMultiTransformer(D, HEADS, FFN, num_layers=LAYERS)
    return TokenServingModel(core, _EMBED, _HEAD)


def _adversarial_draft():
    paddle.seed(99)
    core = FusedMultiTransformer(D, HEADS, FFN, num_layers=1)
    return TokenServingModel(core, _EMBED, _HEAD)


def _prompt(n=9, seed=42):
    rng = np.random.default_rng(seed)
    return list(rng.integers(0, VOCAB, n))


def _eng(tsm, draft=None, **kw):
    kws = dict(k=0, max_batch=4, block_size=BS, num_blocks=60,
               max_blocks_per_seq=MB)
    kws.update(kw)
    return SpeculativeEngine(tsm, draft, **kws)


def _serve_group(e, gid, n, n_gen, max_rounds=200):
    """Step until the group has all n branch rids and every branch
    generated n_gen tokens. Returns streams in branch order."""
    for _ in range(max_rounds):
        g = e.group(gid)
        if g is not None and len(g["rids"]) == n and \
                all(r in e._by_rid and len(e.generated(r)) >= n_gen
                    for r in g["rids"]):
            return [e.generated(r)[:n_gen] for r in g["rids"]]
        e.step()
    raise AssertionError("group serve loop did not converge")


def _serve_rids(e, rids, n_gen, max_rounds=200):
    for _ in range(max_rounds):
        if all(len(e.generated(r)) >= n_gen for r in rids):
            return [e.generated(r)[:n_gen] for r in rids]
        e.step()
    raise AssertionError("serve loop did not converge")


SAMPLED = dict(sampling="top_k", temperature=1.0, top_k=10, seed=1)


# ---------------------------------------------------------------------
# lane seeds + mask registry (pure, engine-free)
# ---------------------------------------------------------------------

class TestLanesAndMasks:
    def test_lane_zero_is_the_seed(self):
        """A lone seeded submit is lane 0 of a group of one — the
        backward-compat clause that keeps old seeded streams stable."""
        assert branch_lane_seed(123, 0) == 123
        lanes = [branch_lane_seed(123, i) for i in range(8)]
        assert len(set(lanes)) == 8
        assert all(0 <= s < 2 ** 32 for s in lanes)
        # lane derivation is position-, not history-, dependent
        assert branch_lane_seed(2 ** 32 - 1, 3) == \
            (2 ** 32 - 1 + 3 * 0x9E3779B9) % 2 ** 32

    def test_mask_registry_is_by_name(self):
        register_logit_mask(
            "test_low_half", lambda toks, V: [t < V // 2
                                              for t in range(V)])
        fn = logit_mask_fn("test_low_half")
        assert fn([1, 2], 10) == [True] * 5 + [False] * 5
        with pytest.raises(KeyError, match="no_such_mask"):
            logit_mask_fn("no_such_mask")
        with pytest.raises(ValueError, match="callable"):
            register_logit_mask("bad", 42)

    def test_submit_validations(self):
        e = _eng(_target())
        with pytest.raises(ValueError, match="n must be"):
            e.submit(_prompt(), n=0)
        with pytest.raises(ValueError, match="best_of"):
            e.submit(_prompt(), best_of=True)
        with pytest.raises(KeyError, match="never_registered"):
            e.submit(_prompt(), logit_mask="never_registered")
        with pytest.raises(ValueError, match="one branch"):
            e.submit(_prompt(), resume=True, n=2)
        with pytest.raises(ValueError, match="max_batch"):
            e.submit(_prompt(), n=99)


# ---------------------------------------------------------------------
# greedy groups: one prefill, n identical streams
# ---------------------------------------------------------------------

class TestGreedyGroup:
    def test_group_matches_lone_stream_and_prices_one_prefill(self):
        p = _prompt()
        e = _eng(_target())
        gid = e.submit(p, n=4)
        streams = _serve_group(e, gid, 4, 10)
        e.check_invariants()

        e1 = _eng(_target())
        lone = _serve_rids(e1, [e1.submit(p)], 10)[0]
        assert streams == [lone] * 4     # greedy branches never fork
        ps = e.engine.parallel_stats
        assert ps.groups == 1 and ps.branches == 3
        assert ps.prefill_tokens_saved == 3 * len(p)
        assert ps.branches_per_group == 3.0
        # one-charge-per-reference: 4 tables over one prompt's pages
        assert ps.shared_blocks == 3 * e.engine.cache.blocks_needed(
            len(p))

    def test_prefix_cache_and_int8_compose(self):
        """The group transform composes with prefix caching and int8
        KV pages: each variant's group streams equal that variant's
        lone stream (int8 diverges from fp32 — the group must not
        diverge from its OWN serving mode)."""
        rng = np.random.default_rng(7)
        p = list(rng.integers(0, VOCAB, 2 * BS + 5))
        for kw in (dict(prefix_cache=True), dict(kv_dtype="int8")):
            e = _eng(_target(), **kw)
            gid = e.submit(p, n=3)
            streams = _serve_group(e, gid, 3, 8)
            e.check_invariants()
            e1 = _eng(_target(), **kw)
            lone = _serve_rids(e1, [e1.submit(p)], 8)[0]
            assert streams == [lone] * 3, kw


# ---------------------------------------------------------------------
# the lane oracle: group == n independent lane-seeded runs
# ---------------------------------------------------------------------

class TestSeededLaneOracle:
    N, S, NGEN = 4, 777, 10

    def _oracle(self, eng_kw, draft=None, draft2=None):
        p = _prompt()
        e = _eng(_target(), draft, **eng_kw)
        gid = e.submit(p, n=self.N, seed=self.S)
        group = _serve_group(e, gid, self.N, self.NGEN)
        e.check_invariants()

        e2 = _eng(_target(), draft2, **eng_kw)
        rids = [e2.submit(p, seed=branch_lane_seed(self.S, i))
                for i in range(self.N)]
        independent = _serve_rids(e2, rids, self.NGEN)
        assert group == independent
        # the oracle is vacuous unless sampling actually diverged
        assert len(set(map(tuple, group))) > 1, \
            "branches never diverged — the lane oracle proved nothing"
        return e

    def test_plain_sampling(self):
        self._oracle(dict(**SAMPLED))

    @pytest.mark.spec
    def test_speculative_rollback_sampling(self):
        """Adversarial draft: near-every round rejects mid-window, so
        accept/residual draws consume each branch's lane — and the
        group still equals the independent runs (capacity is ample,
        so every slot rides the same L = k+1 window per round in both
        runs — the round-alignment clause lane consumption needs)."""
        e = self._oracle(dict(k=2, **SAMPLED), _adversarial_draft(),
                         _adversarial_draft())
        assert e.stats.rolled_back > 0

    def test_unseeded_groups_share_the_engine_rng(self):
        """No seed: branches draw from the shared engine RNG in slot
        order (no lanes minted) — legal, deterministic per run, but
        NOT the oracle; this pins the opt-in boundary."""
        p = _prompt()
        e = _eng(_target(), **SAMPLED)
        gid = e.submit(p, n=3)
        _serve_group(e, gid, 3, 6)
        assert all(e._by_rid[r].lane is None
                   for r in e.group(gid)["rids"])


# ---------------------------------------------------------------------
# shared pages: refcounts, COW divergence, deep fingerprints
# ---------------------------------------------------------------------

class TestSharedPages:
    def test_refcount_equals_branch_tables_then_cow_splits(self):
        rng = np.random.default_rng(7)
        p = list(rng.integers(0, VOCAB, 2 * BS + 5))   # 2 full blocks
        e = _eng(_target(), **SAMPLED)
        gid = e.submit(p, n=4, seed=5)
        # run just far enough that all 4 branches exist and decoded a
        # few tokens (the shared PARTIAL third block COW-split on each
        # branch's first write; the 2 FULL prompt blocks stay shared)
        _serve_group(e, gid, 4, 3)
        peng = e.engine
        g = e.group(gid)
        by_slot = {r.rid: s for s, r in enumerate(peng._requests)
                   if r is not None}
        rep = peng.cache.share_report([by_slot[r] for r in g["rids"]])
        full = len(p) // BS
        assert len(rep["shared_blocks"]) == full
        for b in rep["shared_blocks"]:
            assert rep["multiplicity"][b] == 4
            assert rep["refcount"][b] >= 4
        assert rep["bytes_saved"] == \
            3 * full * BS * peng.cache.kv_bytes_per_token()
        # divergence went through COW: the written tail blocks are
        # private per branch
        tails = [peng.cache.seq_blocks[by_slot[r]][-1]
                 for r in g["rids"]]
        assert len(set(tails)) == 4
        # engine audit (includes the group refcount pass) + the deep
        # pool audit with content fingerprints
        peng.check_invariants()
        peng.cache.check_invariants(lens=peng.lens,
                                    active=peng.active, deep=True)

    def test_group_needs_n_free_slots(self):
        e = _eng(_target(), max_batch=2)
        with pytest.raises(ValueError, match="max_batch"):
            e.submit(_prompt(), n=3)
        # n == max_batch is legal and admits atomically
        gid = e.submit(_prompt(), n=2)
        assert _serve_group(e, gid, 2, 4) is not None
        e.check_invariants()


# ---------------------------------------------------------------------
# best-of-n, caller cancel, fork_stream
# ---------------------------------------------------------------------

class TestBestOfAndBeam:
    def test_best_of_first_finisher_wins_losers_cancelled(self):
        e = _eng(_target(), ledger=CostLedger(), **SAMPLED)
        gid = e.submit(_prompt(), n=3, seed=11, best_of=True)
        for _ in range(200):
            e.step()
            g = e.group(gid)
            if g is not None and g["done"]:
                break
        g = e.group(gid)
        assert g["done"] and g["winner"] in g["rids"]
        e.check_invariants()
        cancelled = [oc for oc in e.outcomes
                     if oc.status == "cancelled"]
        assert {oc.rid for oc in cancelled} == \
            set(g["rids"]) - {g["winner"]}
        # cancellation is an early STOP, not a failure
        assert all(oc.failed for oc in cancelled)   # drops the slot
        assert e.resilience_stats.cancelled == 2
        assert e.resilience_stats.failed == 0
        # pruned branches' pending rows resolved as bestof_pruned
        led = e.ledger
        cons = led.conservation()
        assert cons["ok"], cons
        assert led.totals.waste_rows["bestof_pruned"] > 0

    def test_caller_cancel_detaches_one_branch(self):
        p = _prompt()
        e = _eng(_target())
        gid = e.submit(p, n=3)
        _serve_group(e, gid, 3, 4)
        victim = e.group(gid)["rids"][1]
        partial = e.generated(victim)
        assert e.cancel(victim)
        assert not e.cancel(victim)         # already terminal
        # partial tokens stay readable; survivors keep streaming
        assert e.generated(victim) == partial
        survivors = [r for r in e.group(gid)["rids"] if r != victim]
        streams = _serve_rids(e, survivors, 8)
        e1 = _eng(_target())
        lone = _serve_rids(e1, [e1.submit(p)], 8)[0]
        assert streams == [lone] * 2
        e.check_invariants()

    def test_fork_stream_clones_mid_stream(self):
        """The beam primitive: a clone shares pages at the fork
        length, joins the source's group, and under greedy continues
        the source's exact stream."""
        p = _prompt()
        e = _eng(_target())
        r0 = e.submit(p)
        _serve_rids(e, [r0], 4)
        cut = len(e.generated(r0))
        clone = e.fork_stream(r0)
        g = e.group(e.engine.groups.gid_of(clone))
        assert g["rids"] == [r0, clone]
        a, b = _serve_rids(e, [r0, clone], cut + 6)
        assert a == b                       # greedy: no divergence
        assert e.engine.parallel_stats.branches == 1
        e.check_invariants()


# ---------------------------------------------------------------------
# grammar-constrained decoding: provably in-language
# ---------------------------------------------------------------------

class TestGrammarMask:
    @pytest.mark.spec
    def test_stream_is_provably_in_language(self):
        """Even-tokens-only grammar under the worst case: adversarial
        draft + stochastic sampling + a branch group. Every emitted
        token on every branch must satisfy the mask — the admission
        sample, the draft proposals, the verify sample AND the
        rejection residual all run behind it."""
        register_logit_mask(
            "even_only", lambda toks, V: [t % 2 == 0
                                          for t in range(V)])
        e = _eng(_target(), _adversarial_draft(), k=2, **SAMPLED)
        gid = e.submit(_prompt(), n=3, seed=21, logit_mask="even_only")
        streams = _serve_group(e, gid, 3, 10)
        assert all(t % 2 == 0 for s in streams for t in s), streams
        assert e.stats.rolled_back > 0      # the residual path ran
        e.check_invariants()

    def test_mask_is_stateful_over_the_stream(self):
        """A mask that reads its history: alternate low/high halves
        of the vocabulary by position — proves the hook sees the
        tokens-so-far context at every sampling site."""
        register_logit_mask(
            "alternate_halves",
            lambda toks, V: [(t < V // 2) == (len(toks) % 2 == 0)
                             for t in range(V)])
        e = _eng(_target(), **SAMPLED)
        rid = e.submit(_prompt(), seed=9,
                       logit_mask="alternate_halves")
        (toks,) = _serve_rids(e, [rid], 10)
        plen = len(_prompt())
        for i, t in enumerate(toks):
            low = ((plen + i) % 2 == 0)
            assert (t < VOCAB // 2) == low, (i, t)


# ---------------------------------------------------------------------
# ledger: one charge per shared prefill, conservation with groups
# ---------------------------------------------------------------------

class TestGroupAccounting:
    @pytest.mark.cost
    def test_shared_prefill_priced_once_exactly(self):
        """The exact identity: a greedy n-group's accounted rows are
        the n-independent run's MINUS (n-1) prompt prefills — the
        branches' shared prefill enters the ledger once, under the
        lead."""
        p, n, n_gen = _prompt(12), 3, 6
        grp_led, ind_led = CostLedger(), CostLedger()
        e = _eng(_target(), ledger=grp_led)
        _serve_group(e, e.submit(p, n=n), n, n_gen)
        e2 = _eng(_target(), ledger=ind_led)
        _serve_rids(e2, [e2.submit(p) for _ in range(n)], n_gen)
        assert grp_led.conservation()["ok"]
        assert ind_led.conservation()["ok"]
        assert grp_led.totals.rows + (n - 1) * len(p) == \
            ind_led.totals.rows

    @pytest.mark.cost
    @pytest.mark.spec
    def test_conservation_with_groups_rollback_and_pruning(self):
        """The load-bearing identity holds with every group mechanism
        firing at once: spec rollback waste, best-of pruning waste,
        and fork-raised high-water marks."""
        led = CostLedger()
        e = _eng(_target(), _adversarial_draft(), k=2, ledger=led,
                 **SAMPLED)
        gid = e.submit(_prompt(), n=3, seed=31, best_of=True)
        for _ in range(250):
            e.step()
            g = e.group(gid)
            if g is not None and g["done"] and \
                    len(e.outcomes) >= 2:
                break
        assert e.group(gid)["done"]
        for rid in list(e.group(gid)["rids"]):
            if rid in e._by_rid:
                e.release(rid)
        cons = led.conservation()
        assert cons["ok"], cons
        assert cons["rows"]["pending"] == 0
        t = led.totals
        assert t.waste_rows["bestof_pruned"] > 0
        assert t.waste_rows["spec_rejected"] > 0
        assert e.stats.rolled_back > 0


# ---------------------------------------------------------------------
# crash mid-group: recoverable replay keeps every branch stream
# ---------------------------------------------------------------------

class TestRecoverableGroups:
    @pytest.mark.recovery
    def test_crash_mid_group_replays_bit_identical(self, tmp_path):
        """Budget-mode prefill spreads the group's one prefill across
        live rounds, so the post_prefill crash fires RIGHT AFTER the
        scheduler forked the branches — the snapshot/journal replay
        must rebuild the branch slots, the group table and every RNG
        lane, and the streams must equal the uninterrupted run's."""
        p, n, n_gen, S = _prompt(), 3, 10, 99
        kw = dict(k=2, prefill_token_budget=4, **SAMPLED)

        def drive(srv, gid, tsm, jp=None, sp=None, inj=None):
            restores = 0
            for _ in range(300):
                g = srv.engine.group(gid) \
                    if isinstance(srv, RecoverableServer) \
                    else srv.group(gid)
                if g is not None and len(g["rids"]) == n and \
                        all(len(srv.generated(r)) >= n_gen
                            for r in g["rids"]):
                    return srv, g, restores
                try:
                    srv.step()
                except EngineCrash:
                    srv = RecoverableServer.recover(
                        tsm, None, journal_path=jp, snapshot_path=sp,
                        injector=inj)
                    srv.check_invariants()
                    restores += 1
            raise AssertionError("group recovery did not converge")

        tsm = _target()
        e = _eng(tsm, **kw)
        e, g, _ = drive(e, e.submit(p, n=n, seed=S), tsm)
        base = {r: e.generated(r)[:n_gen] for r in g["rids"]}

        jp = str(tmp_path / "req.wal")
        sp = str(tmp_path / "serve.ckpt")
        tsm2 = _target()
        inj = CrashInjector(crash_at={2: "post_prefill",
                                      3: "post_prefill", 5: "begin"})
        srv = RecoverableServer(_eng(tsm2, injector=inj, **kw),
                                journal_path=jp, snapshot_path=sp,
                                snapshot_every=2)
        gid = srv.submit(p, n=n, seed=S)
        srv, g2, restores = drive(srv, gid, tsm2, jp, sp, inj)
        assert restores >= 2 and inj.crashes >= 2
        got = {r: srv.generated(r)[:n_gen] for r in g2["rids"]}
        assert got == base, "branch streams diverged across crashes"
        srv.check_invariants()


# ---------------------------------------------------------------------
# telemetry: branch gauges
# ---------------------------------------------------------------------

class TestGroupTelemetry:
    @pytest.mark.obs
    def test_group_summary_gauges_and_series(self):
        col, mon = TraceCollector(), HealthMonitor()
        e = _eng(_target(), collector=col, monitor=mon, **SAMPLED)
        gid = e.submit(_prompt(), n=3, seed=41)
        _serve_group(e, gid, 3, 6)
        # registry: the parallel.* namespace the monitor samples
        reg = e.registry.as_dict()
        assert reg["parallel.groups"] == 1
        assert reg["parallel.branches"] == 2
        assert reg["parallel.branches_per_group"] == 2.0
        # collector: every member is a request record of its own
        assert len(col.requests) == 3
        # monitor: branch gauges series pushed once groups exist
        assert mon.series("parallel.branches_per_group") is not None
        assert mon.series(
            "parallel.branches_per_group").last() == 2.0

    @pytest.mark.obs
    def test_parallel_namespace_dark_without_groups(self):
        """Plain serving leaves parallel.* all zero and the monitor
        series un-pushed — the feature costs nothing when unused."""
        mon = HealthMonitor()
        e = _eng(_target(), monitor=mon)
        _serve_rids(e, [e.submit(_prompt())], 6)
        reg = e.registry.as_dict()
        assert reg["parallel.groups"] == 0
        assert mon.series("parallel.branches_per_group") is None
