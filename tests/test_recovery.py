"""Crash recovery subsystem (inference/recovery.py + the
snapshot/restore surgery in paged_cache.py / scheduler.py /
speculative.py and CrashInjector in resilience.py).

The acceptance bar is CRASH-STORM BIT-IDENTITY: under a seeded
schedule of injected engine deaths (``CrashInjector`` raising
``EngineCrash`` at step boundaries and sub-phases — post-admission,
post-prefill, mid-spec-round, around the journal append), each
recovery rebuilds the engine from the last atomic snapshot plus
deterministic journal replay, and at the end every surviving stream
is BIT-IDENTICAL to an uninterrupted run, every terminal outcome was
delivered exactly once (never lost, never duplicated), and
``check_invariants(deep=True)`` holds after every restore — across
plain, prefix-cached and speculative serving, composed with PR 5's
fault storm."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import FusedMultiTransformer
from paddle_tpu.inference import (CrashInjector, EngineCrash,
                                  FaultInjector, PagedServingEngine,
                                  RecoverableServer, RequestJournal,
                                  RequestOutcome, SnapshotVersionError,
                                  SpeculativeEngine, TokenServingModel,
                                  load_snapshot, read_journal,
                                  save_snapshot)
from paddle_tpu.inference.paged_cache import BlockOOM
from paddle_tpu.inference import recovery as recovery_mod

pytestmark = pytest.mark.recovery

D, HEADS, FFN, LAYERS = 32, 4, 64, 2
VOCAB = 50

_RNG = np.random.RandomState(1234)
_EMBED = _RNG.randn(VOCAB, D).astype(np.float32)


def _model():
    paddle.seed(0)
    return FusedMultiTransformer(D, HEADS, FFN, num_layers=LAYERS)


def _tsm():
    return TokenServingModel(_model(), _EMBED)


# ---------------------------------------------------------------------
# satellite: atomic snapshot persistence
# ---------------------------------------------------------------------

class TestSnapshotStore:
    def test_round_trip_is_atomic_and_bitwise(self, tmp_path):
        path = str(tmp_path / "pool.ckpt")
        payload = {"arr": np.arange(12, dtype=np.float32).reshape(3, 4),
                   "hash": b"\x00\xffchain", "n": 7}
        n = save_snapshot(path, payload)
        assert os.path.getsize(path) == n
        out = load_snapshot(path)
        np.testing.assert_array_equal(out["arr"], payload["arr"])
        assert out["hash"] == payload["hash"] and out["n"] == 7
        # write-temp-then-rename left no temp residue
        assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []
        # overwrite replaces atomically (no append, no corruption)
        save_snapshot(path, {"n": 8})
        assert load_snapshot(path)["n"] == 8

    def test_version_mismatch_is_a_named_error(self, tmp_path):
        import struct
        path = str(tmp_path / "pool.ckpt")
        save_snapshot(path, {"n": 1})
        data = bytearray(open(path, "rb").read())
        struct.pack_into("<I", data, len(recovery_mod.SNAPSHOT_MAGIC),
                         99)
        open(path, "wb").write(bytes(data))
        with pytest.raises(SnapshotVersionError, match="format v99"):
            load_snapshot(path)

    def test_truncation_and_corruption_are_named_errors(self, tmp_path):
        path = str(tmp_path / "pool.ckpt")
        save_snapshot(path, {"arr": np.zeros(64)})
        data = open(path, "rb").read()
        open(path, "wb").write(data[:len(data) // 2])
        with pytest.raises(SnapshotVersionError, match="truncated"):
            load_snapshot(path)
        bad = bytearray(data)
        bad[-1] ^= 0xFF
        open(path, "wb").write(bytes(bad))
        with pytest.raises(SnapshotVersionError, match="CRC"):
            load_snapshot(path)
        open(path, "wb").write(b"definitely not a snapshot file....")
        with pytest.raises(SnapshotVersionError, match="magic"):
            load_snapshot(path)
        open(path, "wb").write(b"\x01")
        with pytest.raises(SnapshotVersionError, match="header"):
            load_snapshot(path)


class TestRequestJournal:
    def test_append_read_seq_and_torn_tail(self, tmp_path):
        path = str(tmp_path / "req.wal")
        j = RequestJournal(path, fresh=True)
        for i in range(3):
            assert j.append("submit", {"i": i}) == i + 1
        j.close()
        recs = read_journal(path)
        assert [(s, k, p["i"]) for s, k, p in recs] == \
            [(1, "submit", 0), (2, "submit", 1), (3, "submit", 2)]
        # crash mid-append: half a record's bytes at the tail
        with open(path, "ab") as f:
            f.write(b"\xff\x00\x00\x00torn")
        assert read_journal(path) == recs
        # reopening TRUNCATES the torn tail, then continues the seq —
        # records appended after recovery stay readable
        j2 = RequestJournal(path)
        assert j2.seq == 3
        j2.append("round", {"emitted": {}})
        j2.close()
        recs2 = read_journal(path)
        assert len(recs2) == 4 and recs2[-1][0] == 4

    def test_mid_file_damage_refuses_not_truncates(self, tmp_path):
        """A CRC hole with intact records BEHIND it is not a torn tail
        (a crash mid-append can only tear the last record): reading or
        reopening must raise RecoveryError, not silently truncate away
        the intact suffix."""
        from paddle_tpu.inference.recovery import RecoveryError
        path = str(tmp_path / "req.wal")
        j = RequestJournal(path, fresh=True)
        offs = [0]
        for i in range(3):
            j.append("submit", {"i": i})
            j._f.flush()
            offs.append(os.path.getsize(path))
        j.close()
        data = bytearray(open(path, "rb").read())
        data[offs[1] + 12] ^= 0xFF      # flip a byte INSIDE record 2
        open(path, "wb").write(bytes(data))
        with pytest.raises(RecoveryError, match="MID-FILE"):
            read_journal(path)
        with pytest.raises(RecoveryError, match="MID-FILE"):
            RequestJournal(path)
        # the file was not touched by the refused open
        assert open(path, "rb").read() == bytes(data)


# ---------------------------------------------------------------------
# engine-level snapshot/restore round trips (embedding surface)
# ---------------------------------------------------------------------

class TestEngineSnapshotRestore:
    def _engine(self, model, **kw):
        base = dict(max_batch=2, block_size=8, num_blocks=24,
                    max_blocks_per_seq=6)
        base.update(kw)
        return PagedServingEngine(model, **base)

    def test_mid_prefill_round_trip_continues_bitwise(self):
        """Snapshot an engine with one slot decoding and one slot
        MID-CHUNKED-PREFILL (token-budget mode); the restored engine
        must hold identical state and produce bitwise-equal hiddens
        for every stepping row from identical inputs."""
        model = _model()
        rng = np.random.RandomState(5)
        eng = self._engine(model, prefix_cache=True, chunk_tokens=8,
                           prefill_token_budget=8)
        eng.submit(paddle.to_tensor(
            rng.randn(6, D).astype(np.float32)))
        eng.submit(paddle.to_tensor(
            rng.randn(30, D).astype(np.float32)))     # long: streams
        x = np.zeros((2, 1, D), np.float32)
        for _ in range(2):       # advance: slot 0 admits, slot 1 mid
            eng.step(paddle.to_tensor(x))
        for rid, slot, h in eng.admitted:
            x[slot, 0] = np.asarray(h.numpy())[0]
        eng.admitted.clear()
        assert eng.num_prefilling == 1    # the long prompt, mid-chunk

        snap = eng.snapshot()
        out = PagedServingEngine.restore(model, snap)
        assert out._step_count == eng._step_count
        np.testing.assert_array_equal(out.lens, eng.lens)
        np.testing.assert_array_equal(out.active, eng.active)
        np.testing.assert_array_equal(out.prefilling, eng.prefilling)
        assert {s: st["pos"] for s, st in out._prefills.items()} == \
            {s: st["pos"] for s, st in eng._prefills.items()}
        assert [r.rid for r in out.queue] == [r.rid for r in eng.queue]

        for _ in range(6):
            a = eng.step(paddle.to_tensor(x))
            b = out.step(paddle.to_tensor(x))
            assert (a is None) == (b is None)
            stepping = eng.active.copy()
            if a is not None:
                av, bv = np.asarray(a.numpy()), np.asarray(b.numpy())
                for slot in np.flatnonzero(stepping):
                    np.testing.assert_array_equal(av[slot], bv[slot])
                for slot in np.flatnonzero(stepping):
                    x[slot, 0] = av[slot, 0]
            for (ra, sa, ha), (rb, sb, hb) in zip(eng.admitted,
                                                  out.admitted):
                assert (ra, sa) == (rb, sb)
                np.testing.assert_array_equal(np.asarray(ha.numpy()),
                                              np.asarray(hb.numpy()))
                x[sa, 0] = np.asarray(ha.numpy())[0]
            eng.admitted.clear()
            out.admitted.clear()
        eng.check_invariants()
        out.check_invariants()

    def test_a_snapshot_of_before_pr30_restores(self):
        """Engine snapshots written before PR 30 carry the engine's
        ``tile_q`` / ``tile_kv`` knobs in their config (always None: no
        caller set them). The knobs are gone; the keys are ignored and
        the snapshot restores as it did."""
        model = _model()
        eng = self._engine(model)
        eng.submit(paddle.to_tensor(np.random.RandomState(6).randn(
            5, D).astype(np.float32)))
        snap = eng.snapshot()
        assert "tile_q" not in snap["config"]
        snap["config"].update(tile_q=None, tile_kv=None)
        out = PagedServingEngine.restore(model, snap)
        np.testing.assert_array_equal(out.lens, eng.lens)
        assert not hasattr(out, "tile_q")
        out.check_invariants()

    def test_deadlines_survive_restore(self):
        """A queued request's step deadline keeps ticking on the
        restored clock and fails at the SAME engine step."""
        model = _model()
        rng = np.random.RandomState(6)
        runs = {}
        for tag in ("live", "restored"):
            eng = self._engine(model, max_batch=1)
            eng.submit(paddle.to_tensor(
                rng.randn(6, D).astype(np.float32)))
            (_, slot, h), = eng.admitted
            eng.admitted.clear()
            eng.submit(paddle.to_tensor(
                rng.randn(6, D).astype(np.float32)), deadline_steps=3)
            x = np.zeros((1, 1, D), np.float32)
            x[slot, 0] = np.asarray(h.numpy())[0]
            eng.step(paddle.to_tensor(x))
            if tag == "restored":
                eng = PagedServingEngine.restore(model, eng.snapshot())
            for _ in range(4):
                eng.step(paddle.to_tensor(x))
            (oc,) = eng.outcomes
            assert oc.status == RequestOutcome.FAILED_DEADLINE
            runs[tag] = oc.step
        assert runs["live"] == runs["restored"]

    def test_restore_rewires_fault_injection(self):
        """Faults keep firing on the restored step clock: an OOM
        scheduled past the snapshot point sheds in the restored
        engine exactly as it would have in the live one."""
        model = _model()
        rng = np.random.RandomState(7)
        prompts = [rng.randn(9, D).astype(np.float32),
                   rng.randn(10, D).astype(np.float32)]

        def run(restore_at):
            inj = FaultInjector(oom_at=[4])
            eng = self._engine(model, injector=inj, num_blocks=30,
                               max_blocks_per_seq=10, block_size=4)
            for p in prompts:
                eng.submit(paddle.to_tensor(p))
            x = np.zeros((2, 1, D), np.float32)
            for _, slot, h in eng.admitted:
                x[slot, 0] = np.asarray(h.numpy())[0]
            eng.admitted.clear()
            sheds = []
            for i in range(6):
                if i == restore_at:
                    eng = PagedServingEngine.restore(
                        model, eng.snapshot(), injector=inj)
                out = eng.step(paddle.to_tensor(x))
                for oc in eng.outcomes:
                    sheds.append((oc.rid, oc.status, oc.step))
                eng.outcomes.clear()
                if out is not None:
                    o = np.asarray(out.numpy())
                    x = o[:, :1].copy()
            return sheds

        assert run(None) == run(2)          # same shed, same step


# ---------------------------------------------------------------------
# tenant state round trips (multi-tenant isolation, PR 7)
# ---------------------------------------------------------------------

class TestTenantSnapshotRestore:
    def _engine(self, model, **kw):
        base = dict(max_batch=3, block_size=4, num_blocks=40,
                    max_blocks_per_seq=10,
                    tenants={"a": {"quota_blocks": 8, "weight": 2.0},
                             "b": {"reserved_blocks": 6}})
        base.update(kw)
        return PagedServingEngine(model, **base)

    def test_quotas_weights_stats_queue_order_survive_restore(self):
        """Satellite: tenant configs, WFQ virtual times, per-tenant
        stats, per-tenant block charges and the queue order all
        round-trip snapshot()/restore(), and the restored engine
        ADMITS identically (the WFQ state is scheduler state)."""
        model = _model()
        rng = np.random.RandomState(41)
        eng = self._engine(model)
        # fill the 3 slots and build a mixed queue behind them
        for t in ("a", "b", None):
            eng.submit(paddle.to_tensor(
                rng.randn(8, D).astype(np.float32)), tenant_id=t)
        queued = [eng.submit(paddle.to_tensor(
            rng.randn(6, D).astype(np.float32)), tenant_id=t)
            for t in ("b", "a", "b", None)]
        x = np.zeros((3, 1, D), np.float32)
        for _, slot, h in eng.admitted:
            x[slot, 0] = np.asarray(h.numpy())[0]
        eng.admitted.clear()
        for _ in range(3):
            eng.step(paddle.to_tensor(x))
        eng.check_invariants()

        out = PagedServingEngine.restore(model, eng.snapshot())
        assert list(out.tenants) == list(eng.tenants)
        for tid in eng.tenants:
            a, b = eng.tenants[tid], out.tenants[tid]
            assert (a.quota_blocks, a.reserved_blocks, a.weight,
                    a.vtime) == (b.quota_blocks, b.reserved_blocks,
                                 b.weight, b.vtime)
            assert a.stats.as_dict() == b.stats.as_dict()
            assert eng.cache.tenant_charge(tid) == \
                out.cache.tenant_charge(tid)
        assert out._vclock == eng._vclock
        assert [r.rid for r in out.queue] == [r.rid for r in eng.queue]
        assert [r.tenant for r in out.queue] == \
            [r.tenant for r in eng.queue]
        out.check_invariants()
        # both engines must now run the SAME weighted-fair admission
        # sequence as slots free up
        for e in (eng, out):
            e.release(0)
            e.release(1)
        assert [(r, s) for r, s, _ in eng.admitted] == \
            [(r, s) for r, s, _ in out.admitted]

    def test_pre_tenant_snapshot_version_gates(self):
        """A PR 6-era snapshot (no tenants key, no per-request tenant,
        no seq_tenant in the pool) restores onto the implicit default
        tenant instead of crashing — and the charge audit holds."""
        from paddle_tpu.inference import DEFAULT_TENANT
        model = _model()
        rng = np.random.RandomState(42)
        eng = PagedServingEngine(model, max_batch=2, block_size=4,
                                 num_blocks=24, max_blocks_per_seq=6)
        eng.submit(paddle.to_tensor(rng.randn(7, D).astype(np.float32)))
        eng.submit(paddle.to_tensor(rng.randn(9, D).astype(np.float32)))
        snap = eng.snapshot()
        # strip every tenant-era field, as a pre-PR-7 build wrote it
        del snap["tenants"]
        del snap["vclock"]
        for rec in snap["requests"]:
            del rec["tenant"]
        del snap["cache"]["seq_tenant"]
        out = PagedServingEngine.restore(model, snap)
        assert list(out.tenants) == [DEFAULT_TENANT]
        held = out.cache.tenant_charge(DEFAULT_TENANT)
        assert held == out.cache.blocks_in_use > 0
        out.check_invariants()

    def test_set_tenant_journaled_and_replayed(self, tmp_path):
        """Runtime set_tenant calls ride the journal: a crash after a
        mid-run reconfiguration replays it, so the recovered engine
        enforces the NEW quota (snapshot_every=0 forces the whole
        journal through replay)."""
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(43)
        inj = CrashInjector(crash_at={4: "post_journal"})
        srv = _server(tsm, None, jp, sp, injector=inj,
                      snapshot_every=0, max_batch=2)
        srv.set_tenant("t", quota_blocks=4, weight=2.0)
        r0 = srv.submit(list(rng.integers(0, VOCAB, 6)),
                        tenant_id="t")
        crashes = 0
        for _ in range(10):
            try:
                srv.step()
            except EngineCrash:
                crashes += 1
                srv = RecoverableServer.recover(
                    tsm, None, journal_path=jp, snapshot_path=sp,
                    injector=inj)
                srv.check_invariants()
        assert crashes == 1
        ten = srv.engine.engine.tenants["t"]
        assert ten.quota_blocks == 4 and ten.weight == 2.0
        kinds = [k for _, k, _ in read_journal(jp)]
        assert "set_tenant" in kinds
        # and a rejection against the replayed quota is delivered
        # exactly once across a second recovery
        big = list(rng.integers(0, VOCAB, 30))     # 8 blocks > 4
        rej = srv.submit(big, tenant_id="t")
        delivered = [oc for oc in srv.drain_outcomes()
                     if oc.rid == rej]
        assert len(delivered) == 1
        assert delivered[0].status == RequestOutcome.REJECTED_ADMISSION
        srv.step()      # journals the drain record
        srv2 = RecoverableServer.recover(tsm, None, journal_path=jp,
                                         snapshot_path=sp)
        assert all(oc.rid != rej for oc in srv2.drain_outcomes())


# ---------------------------------------------------------------------
# recoverable server: exactly-once outcomes, pool rehoming
# ---------------------------------------------------------------------

def _paths(tmp_path):
    return (str(tmp_path / "req.wal"), str(tmp_path / "serve.ckpt"))


def _server(tsm, draft, jp, sp, *, injector=None, snapshot_every=2,
            **eng_kw):
    kw = dict(k=0, max_batch=2, block_size=4, num_blocks=60,
              max_blocks_per_seq=10)
    kw.update(eng_kw)
    eng = SpeculativeEngine(tsm, draft, injector=injector, **kw)
    return RecoverableServer(eng, journal_path=jp, snapshot_path=sp,
                             snapshot_every=snapshot_every)


class TestJournalCompaction:
    """Satellite: journal compaction at snapshot boundaries — records
    a durable snapshot covers are dropped (they can never replay:
    recovery skips seq <= the snapshot's journal_seq), bounding the
    journal on a long-running server. The compact marker reuses the
    covered seq so the lineage check, seq numbering and the
    lag/bytes gauges all stay correct."""

    def test_compact_drops_covered_records_atomically(self, tmp_path):
        path = str(tmp_path / "req.wal")
        j = RequestJournal(path, fresh=True)
        for i in range(6):
            j.append("submit", {"i": i})
        before = j.bytes_written
        assert before == os.path.getsize(path)
        reclaimed = j.compact(4)
        assert reclaimed > 0
        assert j.bytes_written == os.path.getsize(path) < before
        # marker (seq 4) + survivors 5, 6; seq numbering continues
        recs = read_journal(path)
        assert [(s, k) for s, k, _ in recs] == \
            [(4, "compact"), (5, "submit"), (6, "submit")]
        assert j.append("release", {"rid": 0}) == 7
        # idempotent: nothing left at/below 4 but the marker
        assert j.compact(4) == 0
        j.close()
        assert [s for s, _, _ in read_journal(path)] == [4, 5, 6, 7]
        assert [f for f in os.listdir(tmp_path)
                if ".compact." in f] == []

    def test_snapshot_compacts_and_gauges_stay_correct(self, tmp_path):
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(21)
        srv = _server(tsm, None, jp, sp, snapshot_every=2)
        reg = srv.engine.registry
        # fresh server: snapshot 0's compaction is a no-op, the bytes
        # gauge starts at zero
        assert reg.as_dict()["journal.bytes"] == 0
        for p in [list(rng.integers(0, VOCAB, 6)) for _ in range(2)]:
            srv.submit(p)
        grown = reg.as_dict()["journal.bytes"]
        assert grown > 0
        sizes = []
        for _ in range(4):
            srv.step()
            d = reg.as_dict()
            assert d["journal.bytes"] == srv.journal.bytes_written \
                == os.path.getsize(jp)
            sizes.append(d["journal.bytes"])
        # the periodic snapshots really compacted: the file shrank at
        # a snapshot boundary instead of growing monotonically
        assert any(b < a for a, b in zip(sizes, sizes[1:])), \
            f"journal never shrank: {sizes}"
        assert reg.as_dict()["journal.lag_records"] == \
            srv.journal.seq - srv._snap_seq
        srv.close()

    def test_recovery_from_compacted_journal(self, tmp_path):
        """Crash AFTER a compacting snapshot plus a few more rounds:
        the lineage check accepts the compacted journal (marker seq ==
        snapshot seq), replay runs only the surviving suffix, and the
        recovered stream is bit-identical to an uninterrupted run."""
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(22)
        prompts = [list(rng.integers(0, VOCAB, 7)) for _ in range(2)]

        def run(inj):
            srv = _server(_tsm(), None, jp, sp, injector=inj,
                          snapshot_every=2)
            rids = [srv.submit(p) for p in prompts]
            crashes = 0
            for _ in range(20):
                if all(len(srv.generated(r)) >= 6 for r in rids):
                    break
                try:
                    srv.step()
                except EngineCrash:
                    crashes += 1
                    srv = RecoverableServer.recover(
                        tsm, None, journal_path=jp, snapshot_path=sp,
                        injector=inj)
                    srv.check_invariants()
            out = {r: srv.generated(r)[:6] for r in rids}
            srv.close()
            return out, crashes

        clean, _ = run(None)
        # crash at round 5: snapshots (and compactions) fired at
        # rounds 2 and 4, so the journal at crash time is compacted
        stormy, crashes = run(CrashInjector(crash_at={5: "begin"}))
        assert crashes == 1
        assert stormy == clean
        # the compaction really happened before the crash: the
        # journal's first record is a compact marker
        recs = read_journal(jp)
        assert recs[0][1] == "compact"

    def test_compact_journal_false_keeps_history(self, tmp_path):
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(23)
        eng = SpeculativeEngine(tsm, None, k=0, max_batch=2,
                                block_size=4, num_blocks=60,
                                max_blocks_per_seq=10)
        srv = RecoverableServer(eng, journal_path=jp,
                                snapshot_path=sp, snapshot_every=2,
                                compact_journal=False)
        srv.submit(list(rng.integers(0, VOCAB, 6)))
        for _ in range(5):
            srv.step()
        kinds = [k for _, k, _ in read_journal(jp)]
        assert "compact" not in kinds and kinds.count("round") == 5
        srv.close()


class TestServerHygiene:
    """Satellite: RecoverableServer/RequestJournal shutdown + re-entry
    hygiene — close() and repeated recover() are idempotent, a clean
    journal reopens untouched (no gratuitous truncate), and a FAILED
    replay releases its journal fd instead of leaking it."""

    def test_close_is_idempotent(self, tmp_path):
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        srv = _server(tsm, None, jp, sp)
        srv.submit([1, 2, 3, 4])
        srv.step()
        srv.drain_outcomes()
        srv.close()
        assert srv.journal.closed
        size = os.path.getsize(jp)
        srv.close()                      # second close: clean no-op
        srv.close()
        assert srv.journal.closed
        assert os.path.getsize(jp) == size
        # the journal itself is also double-close safe
        j = RequestJournal(str(tmp_path / "x.wal"), fresh=True)
        j.append("submit", {"i": 0})
        j.close()
        j.close()
        assert j.closed

    def test_clean_journal_reopen_leaves_bytes_untouched(
            self, tmp_path):
        """No torn tail => no truncate: reopening an INTACT journal
        must not rewrite the file (repeated recover cycles used to
        re-truncate at the same length on every open)."""
        path = str(tmp_path / "req.wal")
        j = RequestJournal(path, fresh=True)
        for i in range(3):
            j.append("submit", {"i": i})
        j.close()
        before = open(path, "rb").read()
        j2 = RequestJournal(path)        # clean reopen: pure append
        assert j2.seq == 3
        assert open(path, "rb").read() == before
        j2.append("round", {"emitted": {}})
        j2.close()
        assert open(path, "rb").read()[:len(before)] == before
        # a TORN tail still gets cut exactly once
        with open(path, "ab") as f:
            f.write(b"\x99\x00\x00\x00torn")
        j3 = RequestJournal(path)
        assert j3.seq == 4
        j3.close()
        assert b"torn" not in open(path, "rb").read()

    def test_repeated_recover_is_idempotent(self, tmp_path):
        """Recovering twice from the same files (retiring the first
        incarnation in between) yields the same serving state both
        times — no double-truncate, no seq drift, no fd leak."""
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(31)
        inj = CrashInjector(crash_at={3: "begin"})
        srv = _server(tsm, None, jp, sp, injector=inj)
        r1 = srv.submit(list(rng.integers(0, VOCAB, 6)))
        with pytest.raises(EngineCrash):
            for _ in range(5):
                srv.step()
        rec1 = RecoverableServer.recover(
            tsm, None, journal_path=jp, snapshot_path=sp)
        state1 = (rec1.engine.generated(r1), rec1.journal.seq,
                  rec1.rounds)
        rec1.close()
        rec2 = RecoverableServer.recover(
            tsm, None, journal_path=jp, snapshot_path=sp)
        assert (rec2.engine.generated(r1), rec2.journal.seq,
                rec2.rounds) == state1
        rec2.step()
        assert len(rec2.engine.generated(r1)) > len(state1[0])
        rec2.check_invariants()
        rec2.close()

    def test_failed_replay_releases_the_journal_fd(self, tmp_path,
                                                   monkeypatch):
        """A replay that diverges (RecoveryError) abandons the
        half-built server — its journal append handle must be CLOSED
        on the way out, not leaked holding the WAL open."""
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(32)
        srv = _server(tsm, None, jp, sp, snapshot_every=0)
        r1 = srv.submit(list(rng.integers(0, VOCAB, 6)))
        for _ in range(2):
            srv.step()
        srv.close()
        # corrupt determinism: rewrite one journaled round's emitted
        # tokens (seq numbering preserved) so replay must diverge
        recs = read_journal(jp)
        j = RequestJournal(jp, fresh=True)
        for seq, kind, payload in recs:
            if kind == "round" and payload["emitted"].get(r1):
                payload = {"emitted": {
                    r1: [t + 1 for t in payload["emitted"][r1]]}}
            j.seq = seq - 1
            j.append(kind, payload)
        j.close()
        opened = []
        real = recovery_mod.RequestJournal

        class Spy(real):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                opened.append(self)
        monkeypatch.setattr(recovery_mod, "RequestJournal", Spy)
        with pytest.raises(recovery_mod.RecoveryError,
                           match="diverged"):
            RecoverableServer.recover(tsm, None, journal_path=jp,
                                      snapshot_path=sp)
        assert opened and all(jj.closed for jj in opened)


class TestExactlyOnceOutcomes:
    def test_drained_outcome_not_redelivered_after_crash(self, tmp_path):
        """The outcome is drained (journaled) BEFORE the crash: replay
        regenerates it inside the engine, the drain record suppresses
        it — delivered exactly once."""
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(8)
        inj = CrashInjector(crash_at={4: "post_journal"})
        srv = _server(tsm, None, jp, sp, injector=inj, max_batch=1)
        srv.submit(list(rng.integers(0, VOCAB, 6)))
        r1 = srv.submit(list(rng.integers(0, VOCAB, 6)),
                        deadline_steps=2)     # queued: times out step 3
        delivered, crashes = [], 0
        for _ in range(8):
            try:
                srv.step()
                delivered += srv.drain_outcomes()
            except EngineCrash:
                crashes += 1
                srv = RecoverableServer.recover(
                    tsm, None, journal_path=jp, snapshot_path=sp,
                    injector=inj)
                srv.check_invariants()
        assert crashes == 1
        rids = [oc.rid for oc in delivered]
        assert rids.count(r1) == 1
        oc = next(o for o in delivered if o.rid == r1)
        assert oc.status == RequestOutcome.FAILED_DEADLINE

    def test_undrained_outcome_not_lost_after_crash(self, tmp_path):
        """The crash lands in the SAME round the outcome is produced,
        before anything reaches the journal: the round replays live
        after recovery and the outcome is delivered — exactly once,
        the other direction."""
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(9)
        inj = CrashInjector(crash_at={3: "pre_journal"})
        srv = _server(tsm, None, jp, sp, injector=inj, max_batch=1)
        srv.submit(list(rng.integers(0, VOCAB, 6)))
        r1 = srv.submit(list(rng.integers(0, VOCAB, 6)),
                        deadline_steps=2)
        delivered, crashes = [], 0
        for _ in range(8):
            try:
                srv.step()
                delivered += srv.drain_outcomes()
            except EngineCrash:
                crashes += 1
                srv = RecoverableServer.recover(
                    tsm, None, journal_path=jp, snapshot_path=sp,
                    injector=inj)
                srv.check_invariants()
        assert crashes == 1
        rids = [oc.rid for oc in delivered]
        assert rids.count(r1) == 1

    def test_wall_clock_deadlines_rejected_up_front(self, tmp_path):
        """deadline_s is wall-clock: a replayed round's wall time is
        not the live round's, so it cannot replay deterministically —
        the journaled server refuses it at submit instead of blowing
        up a future recovery with RecoveryError (deadline_steps is the
        deterministic equivalent; bare engines still take
        deadline_s)."""
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        srv = _server(tsm, None, jp, sp)
        with pytest.raises(ValueError, match="deadline_steps"):
            srv.submit([1, 2, 3], deadline_s=5.0)
        # nothing reached the journal or the engine
        assert [k for _, k, _ in read_journal(jp)] == []
        assert not srv.engine.engine.queue
        srv.submit([1, 2, 3], deadline_steps=5)     # fine

    def test_rejected_submits_do_not_poison_replay(self, tmp_path):
        """A submission the engine REJECTS (empty prompt,
        over-capacity, unknown rid release) hits the journal before
        validation fires; replay must skip those records — the live
        call raised before any engine mutation, so they are
        deterministic no-ops — instead of re-raising a raw ValueError
        out of recover() and bricking the lineage forever
        (snapshot_every=0: recovery replays the FULL journal,
        poisoned records included)."""
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(11)
        prompt = list(rng.integers(0, VOCAB, 6))
        inj = CrashInjector(crash_at={3: "post_journal"})
        srv = _server(tsm, None, jp, sp, injector=inj,
                      snapshot_every=0)
        r0 = srv.submit(prompt)
        with pytest.raises(ValueError):
            srv.submit([])                       # journaled, rejected
        with pytest.raises(ValueError):
            srv.submit(list(rng.integers(0, VOCAB, 999)))  # > capacity
        with pytest.raises(KeyError):
            srv.release(12345)                   # unknown rid
        kinds = [k for _, k, _ in read_journal(jp)]
        assert kinds.count("submit") == 3 and "release" in kinds
        crashes = 0
        for _ in range(20):
            try:
                srv.step()
            except EngineCrash:
                crashes += 1
                srv = RecoverableServer.recover(
                    tsm, None, journal_path=jp, snapshot_path=sp,
                    injector=inj)
                srv.check_invariants()
            if len(srv.generated(r0)) >= 6:
                break
        assert crashes == 1
        # the survivor streams bit-identically to a clean run
        clean = _server(_tsm(), None, str(tmp_path / "c.wal"),
                        str(tmp_path / "c.ckpt"))
        rc = clean.submit(prompt)
        for _ in range(20):
            clean.step()
            if len(clean.generated(rc)) >= 6:
                break
        assert srv.generated(r0)[:6] == clean.generated(rc)[:6]

    def test_recover_refuses_foreign_journal(self, tmp_path):
        """A journal ending BEFORE the snapshot's journal_seq is not
        this snapshot's journal (lost file, stale backup, wrong path):
        recovering from it would reuse seqs the next recovery silently
        skips — every post-recovery request would vanish. recover()
        must refuse with RecoveryError instead."""
        from paddle_tpu.inference.recovery import RecoveryError
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        srv = _server(tsm, None, jp, sp, snapshot_every=1)
        srv.submit([1, 2, 3])
        srv.step()                  # snapshot now covers seq >= 2
        srv.close()
        os.remove(jp)               # the journal is lost
        with pytest.raises(RecoveryError, match="lineage"):
            RecoverableServer.recover(tsm, None, journal_path=jp,
                                      snapshot_path=sp)


class TestPoolRehoming:
    def _baseline(self, tsm, prompts, n_gen):
        eng = SpeculativeEngine(tsm, None, k=0, max_batch=2,
                                block_size=4, num_blocks=60,
                                max_blocks_per_seq=10)
        rids = [eng.submit(p) for p in prompts]
        for _ in range(n_gen + 2):
            eng.step()
        return {r: eng.generated(r)[:n_gen] for r in rids}

    def test_recover_into_larger_pool_continues_bitwise(self, tmp_path):
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(10)
        prompts = [list(rng.integers(0, VOCAB, 7)) for _ in range(3)]
        base = self._baseline(tsm, prompts, 12)
        srv = _server(tsm, None, jp, sp, snapshot_every=2)
        rids = [srv.submit(p) for p in prompts]
        for _ in range(5):
            srv.step()
        # "crash" and rehome into a pool twice the size
        srv = RecoverableServer.recover(
            tsm, None, journal_path=jp, snapshot_path=sp,
            num_blocks=120)
        srv.check_invariants()
        assert srv.engine.engine.cache.num_blocks == 120
        for _ in range(9):
            srv.step()
        for r in rids:
            assert srv.generated(r)[:12] == base[r], \
                "stream diverged after rehoming into a larger pool"

    def test_recover_into_too_small_pool_is_precise_oom(self, tmp_path):
        tsm = _tsm()
        jp, sp = _paths(tmp_path)
        rng = np.random.default_rng(11)
        srv = _server(tsm, None, jp, sp, snapshot_every=2)
        for _ in range(3):
            srv.submit(list(rng.integers(0, VOCAB, 9)))
        for _ in range(4):
            srv.step()
        live = int((srv.engine.engine.cache.allocator
                    .refcount[1:] > 0).sum())
        with pytest.raises(BlockOOM, match="restore needs"):
            RecoverableServer.recover(tsm, None, journal_path=jp,
                                      snapshot_path=sp,
                                      num_blocks=live)      # < live+1


# ---------------------------------------------------------------------
# THE HEADLINE: seeded crash storm, bit-identical surviving streams,
# exactly-once outcomes, deep invariants after every restore.
# ---------------------------------------------------------------------

def _drive_plain(tsm, draft, prompts, n_gen, *, injector=None,
                 max_iters=200, **eng_kw):
    """Uninterrupted reference run: the bare SpeculativeEngine (the
    server is a passthrough), optionally under the same FAULT schedule
    a composed storm uses."""
    kw = dict(k=0, max_batch=2, block_size=4, num_blocks=60,
              max_blocks_per_seq=10)
    kw.update(eng_kw)
    eng = SpeculativeEngine(tsm, draft, injector=injector, **kw)
    rids = [eng.submit(p) for p in prompts]
    done, failed = {}, {}
    for _ in range(max_iters):
        live = [r for r in rids if r not in done and r not in failed]
        if not live:
            break
        eng.step()
        for oc in eng.outcomes:
            if oc.failed:
                failed[oc.rid] = oc
        eng.outcomes.clear()
        for r in live:
            if r in failed:
                continue
            if len(eng.generated(r)) >= n_gen:
                done[r] = eng.generated(r)[:n_gen]
                eng.release(r)
    else:
        raise AssertionError("plain driver did not converge")
    return done, failed


def _drive_recoverable(tsm, draft, prompts, n_gen, jp, sp, injector, *,
                       snapshot_every=2, max_iters=300, **eng_kw):
    """The crash-storm driver: serve through RecoverableServer, treat
    every EngineCrash as a process death — abandon the server, rebuild
    via recover(), audit deep invariants — and assert outcome
    exactly-once along the way."""
    srv = _server(tsm, draft, jp, sp, injector=injector,
                  snapshot_every=snapshot_every, **eng_kw)
    rids = [srv.submit(p) for p in prompts]
    done, outcomes, failed = {}, {}, set()
    restores = replayed = 0
    for _ in range(max_iters):
        live = [r for r in rids if r not in done and r not in failed]
        if not live:
            break
        try:
            srv.step()
            for oc in srv.drain_outcomes():
                assert oc.rid not in outcomes, \
                    f"outcome for rid {oc.rid} delivered twice"
                outcomes[oc.rid] = oc
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
                tsm, draft, journal_path=jp, snapshot_path=sp,
                injector=injector)
            # the acceptance clause: deep invariants after EVERY
            # restore (engine + pool, incl. content fingerprints)
            srv.check_invariants()
            restores += 1
            replayed += srv.replayed_rounds
    else:
        raise AssertionError("recovery driver did not converge")
    for oc in srv.drain_outcomes():
        assert oc.rid not in outcomes, \
            f"outcome for rid {oc.rid} delivered twice"
        outcomes[oc.rid] = oc
    return done, outcomes, failed, restores, replayed, srv


class TestCrashStormBitIdentity:
    N_GEN = 12

    def _prompts(self, seed, n=4, lo=6, hi=10):
        rng = np.random.default_rng(seed)
        return [list(rng.integers(0, VOCAB, int(L)))
                for L in rng.integers(lo, hi, n)]

    def _storm(self, tmp_path, *, seed, k=0, draft=None, prefix=False,
               fault_kw=None, phases=None, crashes=4, rounds=12):
        tsm = _tsm()
        prompts = self._prompts(seed)
        eng_kw = dict(prefix_cache=prefix, k=k)
        base_inj = FaultInjector(**fault_kw) if fault_kw else None
        base, base_failed = _drive_plain(tsm, draft, prompts,
                                         self.N_GEN,
                                         injector=base_inj, **eng_kw)
        inj = CrashInjector.storm(seed, rounds, crashes=crashes,
                                  phases=phases, **(fault_kw or {}))
        jp, sp = _paths(tmp_path)
        storm, outcomes, failed, restores, replayed, srv = \
            _drive_recoverable(tsm, draft, prompts, self.N_GEN, jp, sp,
                               inj, **eng_kw)
        assert inj.crashes >= min(crashes, 3), \
            f"only {inj.crashes} of {crashes} scheduled crashes fired"
        assert restores == inj.crashes
        # every surviving stream BIT-IDENTICAL to the uninterrupted run
        survivors = 0
        for rid, stream in base.items():
            if rid in failed:
                got = storm.get(rid, srv.generated(rid)
                                if rid in srv.engine._by_rid else [])
                assert got == stream[:len(got)], \
                    "failed stream is not a clean prefix"
            else:
                survivors += 1
                assert storm[rid] == stream, \
                    f"survivor {rid} diverged across the crash storm"
        assert survivors >= 2, "storm left too few survivors to prove"
        # failure sets agree with the fault-only reference run
        assert failed == set(base_failed), \
            "crashes changed WHICH requests failed"
        return inj, outcomes, replayed, srv

    def test_plain_serving_storm(self, tmp_path):
        """ACCEPTANCE (plain paged serving): crashes at step
        boundaries and around the journal append."""
        inj, outcomes, replayed, srv = self._storm(tmp_path, seed=31)
        assert replayed > 0, \
            "no journal replay happened — the storm proved nothing"

    def test_prefix_cached_serving_storm(self, tmp_path):
        """ACCEPTANCE (prefix_cache=True): the chain-hash index and
        cached-free tier round-trip through every restore."""
        inj, outcomes, replayed, srv = self._storm(tmp_path, seed=32,
                                                   prefix=True)
        eng = srv.engine.engine
        assert eng.prefix_cache and eng.cache.prefix_cache

    @pytest.mark.spec
    def test_speculative_serving_storm(self, tmp_path):
        """ACCEPTANCE (speculative k=2): crashes INSIDE the round —
        between draft roll and verify — plus step boundaries; the
        draft pool rebuilds from token streams on every restore."""
        inj, outcomes, replayed, srv = self._storm(
            tmp_path, seed=33, k=2,
            phases=("begin", "mid_spec_round", "pre_journal",
                    "post_journal"))
        assert srv.engine.stats.proposed > 0    # speculation resumed

    def test_storm_composed_with_fault_storm(self, tmp_path):
        """ACCEPTANCE (composition with PR 5): whole-step forced OOMs
        and NaN slots fire on the RESTORED step clock during replay,
        so sheds/quarantines land identically — survivors of
        faults + crashes together still stream bit-identically and
        failure verdicts are delivered exactly once."""
        inj, outcomes, replayed, srv = self._storm(
            tmp_path, seed=34, crashes=3,
            fault_kw=dict(oom_at=[5, 9], nan_at={4: [1]}))
        st = srv.engine.resilience_stats
        assert st.shed >= 1 or st.nan_failed >= 1, \
            "the composed fault schedule never fired"
        delivered_failures = [oc for oc in outcomes.values()
                              if oc.failed]
        assert len(delivered_failures) >= 1
        for oc in delivered_failures:
            assert oc.status in (RequestOutcome.FAILED_OOM,
                                 RequestOutcome.FAILED_NUMERIC)
