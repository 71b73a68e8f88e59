"""Contract-linter self-tests + the tier-1 gate (tools/check_static.py).

Three layers:

  * fixture tests — one tiny synthetic module per pass under
    tests/fixtures/lint/ with a seeded violation (and a suppressed
    one) asserting the EXACT finding: path, line, pass id, and that
    ``# lint: ok(<pass>)`` suppression works and is counted;
  * the tier-1 gate — every pass over the real ``paddle_tpu/`` tree
    must report ZERO unsuppressed findings, so a future PR that adds
    an unserialized field, an unhandled journal kind, an unguarded
    hook touch, an uncharged table mutation or a leaking span fails
    CI the same day it lands, not three PRs later;
  * mutation spot-checks — deleting a single snapshot field, journal
    handler, ``_charge`` call, hook guard or span bracket from a COPY
    of the real source flips the linter to exit 1 with a correct
    ``path:line`` finding (the acceptance criterion).
"""
import json
import os

import pytest

from tools import check_static as cs

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "paddle_tpu")
FIX = os.path.join(REPO, "tests", "fixtures", "lint")
INF = os.path.join(PKG, "inference")


def run(root, passes=None):
    kept, supp, problems, n = cs.run_passes(root, passes)
    assert not problems, problems
    assert n > 0
    return kept, supp


def lineno(path, needle, occurrence=1):
    with open(path) as f:
        hits = [i for i, line in enumerate(f, 1) if needle in line]
    assert len(hits) >= occurrence, f"{needle!r} not in {path}"
    return hits[occurrence - 1]


def by_pass(findings, pass_id):
    return [f for f in findings if f.pass_id == pass_id]


# =====================================================================
# fixture self-tests: exact findings + suppression, one per pass
# =====================================================================

class TestSnapshotFixture:
    ROOT = os.path.join(FIX, "snapshot")

    def test_exact_findings(self):
        kept, supp = run(self.ROOT, ["snapshot-completeness"])
        holder = os.path.join(self.ROOT, "holder.py")
        router = os.path.join(self.ROOT, "router.py")
        got = {(f.path, f.line) for f in kept}
        assert got == {
            (holder, lineno(holder, "self.leaky = 2")),
            (holder, lineno(holder, '"orphan": 0')),
            (router, lineno(router, "self.lost = lost")),
        }
        msgs = sorted(f.msg for f in kept)
        assert any("Holder.leaky" in m for m in msgs)
        assert any("'orphan'" in m for m in msgs)
        assert any("_RouterReq.lost" in m for m in msgs)
        assert all(f.pass_id == "snapshot-completeness" for f in kept)

    def test_suppression(self):
        kept, supp = run(self.ROOT, ["snapshot-completeness"])
        assert {os.path.basename(f.path) for f in supp} == \
            {"holder.py", "router.py"}
        assert all("hushed" in f.msg or "quiet" in f.msg
                   for f in supp)
        assert not any("hushed" in f.msg or "quiet" in f.msg
                       for f in kept)


class TestHotPathFixture:
    ROOT = os.path.join(FIX, "hotpath")

    def test_exact_findings(self):
        kept, supp = run(self.ROOT, ["hot-path-purity"])
        eng = os.path.join(self.ROOT, "engine.py")
        assert {(f.path, f.line) for f in kept} == {
            (eng, lineno(eng, "self.collector.on_step(x)",
                         occurrence=2)),
            (eng, lineno(eng, "t = time.monotonic()")),
        }
        assert all(f.pass_id == "hot-path-purity" for f in kept)
        # guarded touches, __init__ and the cold snapshot() are clean
        assert len(kept) == 2

    def test_suppression(self):
        kept, supp = run(self.ROOT, ["hot-path-purity"])
        assert len(supp) == 1 and "ledger" in supp[0].msg


class TestJournalFixture:
    ROOT = os.path.join(FIX, "journal")

    def test_exact_findings(self):
        kept, supp = run(self.ROOT, ["journal-coverage"])
        rec = os.path.join(self.ROOT, "recovery.py")
        res = os.path.join(self.ROOT, "resilience.py")
        assert {(f.path, f.line) for f in kept} == {
            (rec, lineno(rec, '"orphan"')),
            (res, lineno(res, "FAILED_LOST")),
        }
        assert any("'orphan'" in f.msg for f in kept)
        assert any("FAILED_LOST" in f.msg and "router.py" in f.msg
                   for f in kept)

    def test_suppression(self):
        kept, supp = run(self.ROOT, ["journal-coverage"])
        # BOTH suppression paths must work independently: the
        # journal-kind one and the outcome-member one
        assert any("'hushed'" in f.msg for f in supp)
        assert any("FAILED_QUIET" in f.msg for f in supp)
        assert len(supp) == 2


class TestChargeFixture:
    ROOT = os.path.join(FIX, "charge")

    def test_exact_findings(self):
        kept, supp = run(self.ROOT, ["charge-discipline"])
        pc = os.path.join(self.ROOT, "paged_cache.py")
        assert [(f.path, f.line) for f in kept] == \
            [(pc, lineno(pc, "self.seq_blocks[slot] = []",
                         occurrence=1))]
        assert "MiniCache.bad_clear" in kept[0].msg
        # charging methods (direct and via alias) are clean
        assert len(supp) == 1


class TestSpanFixture:
    ROOT = os.path.join(FIX, "span")

    def test_exact_findings(self):
        kept, supp = run(self.ROOT, ["span-safety"])
        eng = os.path.join(self.ROOT, "engine.py")
        assert [(f.path, f.line) for f in kept] == \
            [(eng, lineno(eng, 'col.span_begin("d")'))]
        assert "bad" in kept[0].msg
        assert len(supp) == 1


class TestExportFixture:
    ROOT = os.path.join(FIX, "export")

    def test_exact_findings(self):
        kept, supp = run(self.ROOT, ["export-drift"])
        init = os.path.join(self.ROOT, "inference", "__init__.py")
        srv = os.path.join(self.ROOT, "inference", "serving.py")
        assert {(f.path, f.line) for f in kept} == {
            (init, lineno(init, "missing_name")),
            (init, lineno(init, "__all__")),
            (srv, lineno(srv, "class OrphanStats")),
        }
        assert any("'Ghost'" in f.msg for f in kept)
        assert any("missing_name" in f.msg for f in kept)
        assert any("OrphanStats" in f.msg for f in kept)
        assert len(supp) == 1 and "QuietStats" in supp[0].msg


class TestCompiledStepFixture:
    ROOT = os.path.join(FIX, "compiledstep")

    def test_exact_findings(self):
        kept, supp = run(self.ROOT, ["compiled-step-purity"])
        cst = os.path.join(self.ROOT, "compiled_step.py")
        srv = os.path.join(self.ROOT, "serving.py")
        assert {(f.path, f.line) for f in kept} == {
            (cst, lineno(cst, "np.asarray(x)")),
            (cst, lineno(cst, "pool.block_until_ready()")),
            (cst, lineno(cst, "np.array(src)")),
            (srv, lineno(srv, "src.tolist()")),
        }
        assert all(f.pass_id == "compiled-step-purity" for f in kept)
        msgs = " | ".join(f.msg for f in kept)
        # the scope labels name the offending function/method
        assert "_pull" in msgs
        assert "CompiledStepRunner._dispatch" in msgs
        assert "ShardedServingCore.forward" in msgs
        # setup boundary (__init__/_setup_weights device_put), the
        # jnp.asarray metadata feed, cold helpers, snapshot readback
        # and out-of-scope classes are all clean
        assert len(kept) == 4

    def test_suppression(self):
        kept, supp = run(self.ROOT, ["compiled-step-purity"])
        assert {os.path.basename(f.path) for f in supp} == \
            {"compiled_step.py", "serving.py"}
        assert any("item()" in f.msg for f in supp)
        assert any("_uncommitted" in f.msg for f in supp)
        assert len(supp) == 2


class TestMoeFixture:
    """Satellite: both contract passes engage a MoE serving core —
    the fixture class shares the real MoeServingCore's name, so it
    inherits the HOT_CLASSES cold-set and the SNAPSHOT_ATTR_ALLOW
    placement entries exactly like the real module does."""

    ROOT = os.path.join(FIX, "moe")

    def test_exact_findings(self):
        core = os.path.join(self.ROOT, "core.py")
        kept, supp = run(self.ROOT,
                         ["snapshot-completeness", "hot-path-purity"])
        assert {(f.path, f.line) for f in kept} == {
            (core, lineno(core, "self.gate_cache = None")),
            (core, lineno(core, '"gate_dtype": "f32"')),
            (core, lineno(core, "self.collector.on_step(x)")),
            (core, lineno(core, "t = time.monotonic()")),
        }
        msgs = " | ".join(f.msg for f in kept)
        assert "MoeServingCore.gate_cache" in msgs
        assert "'gate_dtype'" in msgs
        assert "MoeServingCore.route" in msgs
        # the allowlisted ep placement attrs and the cold moe_metrics
        # clock read produce nothing
        assert "_ep_devices" not in msgs and "_ep_weights" not in msgs
        assert "moe_metrics" not in msgs

    def test_suppression(self):
        kept, supp = run(self.ROOT,
                         ["snapshot-completeness", "hot-path-purity"])
        assert len(supp) == 3
        assert {f.pass_id for f in supp} == \
            {"snapshot-completeness", "hot-path-purity"}


# =====================================================================
# tier-1 gate: the real tree is clean under every pass
# =====================================================================

class TestRealTree:
    def test_zero_findings_all_passes(self):
        """THE gate: the shipped package carries no unsuppressed
        contract violations. A new field/record-kind/lifecycle-op
        that skips its protocol turns this red the day it lands."""
        kept, supp, problems, n = cs.run_passes(PKG)
        assert not problems, problems
        assert n > 100      # the walker really saw the package
        assert kept == [], "\n".join(repr(f) for f in kept)

    def test_passes_engage_real_targets(self):
        """Guard against the linter going vacuously green: each pass
        must actually be analyzing the real contract carriers."""
        files, _ = cs.walk_files(INF)
        snap_classes = {c.name for sf in files for c in sf.classes()
                        if "snapshot" in cs.methods_of(c)
                        and "restore" in cs.methods_of(c)}
        assert {"PagedKVCache", "PagedServingEngine",
                "SpeculativeEngine", "FleetSupervisor",
                "MoeServingCore"} <= snap_classes
        # the fork-shared group table auto-engaged the day it landed:
        # it carries snapshot()/restore(), so its fields ride the
        # completeness audit (mutation spot-check below proves it)
        assert "_GroupTable" in snap_classes
        jc = cs.JournalCoverage()
        kinds = {}
        for sf in files:
            kinds[sf.base] = set(jc._written_kinds(sf))
        assert {"submit", "round", "release", "import_slice",
                "set_tenant", "outcomes", "compact", "cancel"} <= \
            kinds["recovery.py"]
        assert {"submit", "emit", "tick", "delivered", "release",
                "respawn", "rebalance"} <= kinds["router.py"]
        # the outcome taxonomy is discovered, members and all
        members = jc._outcome_members(files)
        assert {"FINISHED", "FAILED_OOM", "FAILED_NUMERIC",
                "FAILED_DEADLINE", "REJECTED_ADMISSION",
                "FAILED_UNROUTABLE", "CANCELLED"} <= set(members)
        # hot classes resolve in the real tree (the sharded serving
        # core included — mesh-era code inherits the purity contract)
        hot = {c.name for sf in files for c in sf.classes()}
        assert {"PagedServingEngine", "SpeculativeEngine",
                "PagedKVCache", "ShardedServingCore",
                "MoeServingCore"} <= hot
        assert "ShardedServingCore" in cs.HOT_CLASSES
        # the MoE core's routing/dispatch path is hot by default: the
        # cold set names only the admin surface, so _moe_ffn /
        # _combine_fold / _ffn_block inherit the purity contract
        assert "MoeServingCore" in cs.HOT_CLASSES
        assert not {"_ffn_block", "_moe_ffn", "_combine_fold"} & \
            cs.HOT_CLASSES["MoeServingCore"]
        # the sharded state holder's geometry really rides snapshots:
        # the harvester sees the ``mp`` key on the REAL PagedKVCache
        # (the mutation spot-check below then proves deleting its
        # restore consumption turns the tree red)
        scp = cs.SnapshotCompleteness()
        for sf in files:
            for c in sf.classes():
                if c.name == "PagedKVCache":
                    keys = scp._snapshot_keys(
                        cs.methods_of(c)["snapshot"])
                    assert "mp" in keys
        # the key-consumed-by-restore leg is NOT vacuous: each real
        # snapshot() yields a non-trivial harvested key set (a
        # refactor that hides the return dict from the harvester
        # must turn this red, not silently vacate the check)
        sc = cs.SnapshotCompleteness()
        for sf in files:
            for c in sf.classes():
                m = cs.methods_of(c)
                if "snapshot" in m and "restore" in m:
                    keys = sc._snapshot_keys(m["snapshot"])
                    # _GroupTable is a two-field holder (groups +
                    # member index) — everything else carries >= 5
                    floor = 2 if c.name == "_GroupTable" else 5
                    assert len(keys) >= floor, (c.name, sorted(keys))
        # the compiled-step purity pass really engages the compiled
        # runner and the serving hand-off: the real tree's one
        # legitimate host hop (the legacy _allreduce device_put)
        # surfaces as a SUPPRESSED finding, never silently out of
        # scope
        kept, supp, problems, _ = cs.run_passes(
            INF, ["compiled-step-purity"])
        assert not problems and kept == []
        assert {os.path.basename(f.path) for f in supp} == \
            {"serving.py"}
        assert len(supp) == 1
        assert any("compiled_step.py" == sf.base for sf in files)

    def test_allowlist_entries_all_load_bearing(self):
        """Anti-rot: every SNAPSHOT_ATTR_ALLOW entry must be NEEDED —
        removing it has to produce a finding. A redundant entry (attr
        also read by snapshot()) would MASK the finding when someone
        later deletes that attr's serialization line."""
        files, _ = cs.walk_files(INF)
        p = cs.SnapshotCompleteness()
        for cls_name, allow in cs.SNAPSHOT_ATTR_ALLOW.items():
            for attr in list(allow):
                saved = allow.pop(attr)
                try:
                    kept = p.run(files)
                finally:
                    allow[attr] = saved
                assert any(f"{cls_name}.{attr} " in f.msg
                           for f in kept), (
                    f"allowlist entry {cls_name}.{attr} is redundant "
                    f"— it would mask a future deletion; remove it")


# =====================================================================
# mutation spot-checks (the acceptance criterion): deleting a single
# protocol site from a COPY of the real source flips exit 0 -> 1 with
# a correct path:line finding
# =====================================================================

def _mutate(tmp_path, src_name, old, new, subdir="m"):
    src = os.path.join(INF, src_name)
    with open(src) as f:
        text = f.read()
    assert old in text, f"mutation anchor gone from {src_name}: {old!r}"
    d = tmp_path / subdir
    d.mkdir(exist_ok=True)
    out = d / src_name
    out.write_text(text.replace(old, new))
    return str(d), str(out)


class TestMutations:
    def test_deleted_snapshot_field(self, tmp_path):
        root, path = _mutate(
            tmp_path, "scheduler.py", '"vclock": self._vclock,', "")
        kept, _ = run(root, ["snapshot-completeness"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "self._vclock ="))]
        assert "_vclock" in kept[0].msg

    def test_deleted_shard_geometry_field(self, tmp_path):
        """The sharded-pool acceptance: the STRUCTURAL snapshot pass
        engaged PagedKVCache's tensor-parallel state the day it
        landed — a restore() that silently drops the recorded mesh
        width (the ``mp`` geometry key) flips exit 0 -> 1 with the
        finding anchored at the serialized key."""
        root, path = _mutate(
            tmp_path, "paged_cache.py",
            'mp_t = int(g.get("mp", 1)) if mp is None else int(mp)',
            "mp_t = 1 if mp is None else int(mp)")
        kept, _ = run(root, ["snapshot-completeness"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, '"mp": self.mp'))]
        assert "'mp'" in kept[0].msg
        assert "never consumed" in kept[0].msg

    def test_deleted_journal_handler(self, tmp_path):
        root, path = _mutate(
            tmp_path, "recovery.py",
            'kind == "release"', 'kind == "release_zzz"')
        kept, _ = run(root, ["journal-coverage"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, 'self.journal.append("release"'))]
        assert "'release'" in kept[0].msg

    def test_deleted_group_snapshot_field(self, tmp_path):
        """The fork-shared group acceptance: the snapshot-completeness
        pass auto-engaged ``_GroupTable`` the day it landed — a
        ``snapshot()`` that silently drops the rid->gid member index
        flips exit 0 -> 1, anchored at the field's declaration."""
        root, path = _mutate(
            tmp_path, "scheduler.py",
            ''',
                "by_rid": dict(self._by_rid)}''', "}")
        kept, _ = run(root, ["snapshot-completeness"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "self._by_rid: Dict[int, int]"))]
        assert "_by_rid" in kept[0].msg

    def test_deleted_cancel_replay_handler(self, tmp_path):
        """A ``recover()`` that stops replaying journaled "cancel"
        records (best-of pruning / caller early stop) flips
        exit 0 -> 1, anchored at the append site."""
        root, path = _mutate(
            tmp_path, "recovery.py",
            'kind == "cancel"', 'kind == "cancel_zzz"')
        kept, _ = run(root, ["journal-coverage"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, 'self.journal.append("cancel"'))]
        assert "'cancel'" in kept[0].msg

    def test_deleted_respawn_replay_handler(self, tmp_path):
        """The fleet WAL acceptance: a ``Router.recover`` that stops
        replaying "respawn" records flips exit 0 -> 1, anchored at
        the (first) write site — capacity history must never be
        journaled-but-dropped."""
        root, path = _mutate(
            tmp_path, "router.py",
            'kind == "respawn"', 'kind == "respawn_zzz"')
        kept, _ = run(root, ["journal-coverage"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, 'self._jrec("respawn"'))]
        assert "'respawn'" in kept[0].msg

    def test_deleted_rebalance_replay_handler(self, tmp_path):
        root, path = _mutate(
            tmp_path, "router.py",
            'kind == "rebalance"', 'kind == "rebalance_zzz"')
        kept, _ = run(root, ["journal-coverage"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, 'self._jrec("rebalance"'))]
        assert "'rebalance'" in kept[0].msg

    def test_deleted_supervisor_snapshot_field(self, tmp_path):
        """The structural snapshot pass engaged ``FleetSupervisor``
        the day it landed: dropping one serialized control-plane
        field (the per-worker attempt history) flips exit 0 -> 1
        anchored at the field's mutation site."""
        root, path = _mutate(
            tmp_path, "fleet.py",
            '"respawn_counts": dict(self.respawn_counts),', "")
        kept, _ = run(root, ["snapshot-completeness"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "self.respawn_counts: Dict"))]
        assert "respawn_counts" in kept[0].msg

    def test_deleted_supervisor_restore_consumption(self, tmp_path):
        """...and the key-consumed-by-restore leg: a restore() that
        silently drops the serialized transport flips red at the
        serialized key."""
        root, path = _mutate(
            tmp_path, "fleet.py",
            'transport=snap["transport"],', "transport='inproc',")
        kept, _ = run(root, ["snapshot-completeness"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, '"transport": self.transport,'))]
        assert "'transport'" in kept[0].msg
        assert "never consumed" in kept[0].msg

    def test_deleted_charge_call(self, tmp_path):
        root, path = _mutate(
            tmp_path, "paged_cache.py",
            "self._charge(slot, -len(drop))", "pass")
        kept, _ = run(root, ["charge-discipline"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "del have[keep:]"))]
        assert "truncate" in kept[0].msg

    def test_deleted_hook_guard(self, tmp_path):
        old = ("        if self.collector is not None:\n"
               "            self.collector.begin_step("
               "self._step_count, kind)")
        new = ("        self.collector.begin_step("
               "self._step_count, kind)")
        root, path = _mutate(tmp_path, "scheduler.py", old, new)
        kept, _ = run(root, ["hot-path-purity"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "self.collector.begin_step"))]
        assert "_begin_step" in kept[0].msg

    def test_deleted_span_bracket(self, tmp_path):
        """The server's ``round`` span is closed on every path by the
        unwinding except in ``step()`` (which is also what makes the
        brackets inside ``_round`` safe): take it away and the
        ``span_begin`` is flagged."""
        old = ("        try:\n"
               "            emitted = self._round(col)\n"
               "        except BaseException:\n"
               "            col.span_unwind(depth, aborted=True)\n"
               "            raise\n")
        new = "        emitted = self._round(col)\n"
        root, path = _mutate(tmp_path, "recovery.py", old, new)
        kept, _ = run(root, ["span-safety"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, 'col.span_begin("round"'))]

    def test_host_pull_in_compiled_dispatch(self, tmp_path):
        """The compiled-collectives acceptance: a host pull sneaking
        onto the per-step dispatch path of the compiled runner — the
        exact regression that re-serializes every step on the host —
        flips exit 0 -> 1 anchored at the offending call."""
        root, path = _mutate(
            tmp_path, "compiled_step.py",
            "pools_g, scales_g = self._assemble(cache)",
            "pools_g, scales_g = self._assemble(cache); "
            "np.asarray(ops)")
        kept, _ = run(root, ["compiled-step-purity"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "np.asarray(ops)"))]
        assert "CompiledStepRunner._dispatch" in kept[0].msg

    def test_host_pull_in_sharded_forward(self, tmp_path):
        """...and on the serving hand-off: ShardedServingCore.forward
        pulling activations to host is flagged the same way."""
        root, path = _mutate(
            tmp_path, "serving.py",
            "res = self._compiled.forward(src, caches, time_step)",
            "res = self._compiled.forward(src, caches, time_step); "
            "src.tolist()")
        kept, _ = run(root, ["compiled-step-purity"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "src.tolist()"))]
        assert "ShardedServingCore.forward" in kept[0].msg

    def test_deleted_moe_snapshot_field(self, tmp_path):
        """MoE engagement acceptance: dropping the routed-row counter
        from MoeServingCore.snapshot() flips exit 0 -> 1 the day it
        happens, anchored at the counter's birth."""
        root, path = _mutate(
            tmp_path, "moe_serving.py", '"rows": self._rows,', "")
        kept, _ = run(root, ["snapshot-completeness"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "self._rows = 0"))]
        assert "MoeServingCore._rows" in kept[0].msg

    def test_deleted_moe_restore_consumption(self, tmp_path):
        """...and a restore() that silently drops the serialized
        kernel-path switch is caught at the serialization site."""
        root, path = _mutate(
            tmp_path, "moe_serving.py",
            'self._use_kernel = cfg["use_kernel"]', "pass")
        kept, _ = run(root, ["snapshot-completeness"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, '"use_kernel": self._use_kernel,'))]
        assert "'use_kernel'" in kept[0].msg
        assert "never consumed" in kept[0].msg

    def test_unguarded_hook_in_moe_dispatch(self, tmp_path):
        """An unguarded hook touch slipped into the per-layer MoE
        dispatch — the hottest loop in the module — is a purity
        finding at the touch site."""
        root, path = _mutate(
            tmp_path, "moe_serving.py",
            "logits = blk.gate(x2)",
            "logits = blk.gate(x2); self.collector.on_step(0)")
        kept, _ = run(root, ["hot-path-purity"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "self.collector.on_step(0)"))]
        assert "MoeServingCore._moe_ffn" in kept[0].msg

    def test_deleted_export(self, tmp_path):
        # renaming an exported name in its source module must trip
        # the import leg of the drift audit
        src_dir = tmp_path / "x" / "inference"
        src_dir.mkdir(parents=True)
        for name in ("__init__.py", "serving.py"):
            with open(os.path.join(INF, name)) as f:
                (src_dir / name).write_text(f.read())
        text = (src_dir / "serving.py").read_text()
        assert "class ContinuousBatchingEngine" in text
        (src_dir / "serving.py").write_text(text.replace(
            "class ContinuousBatchingEngine",
            "class ContinuousBatchingEngineZZZ"))
        kept, _, problems, _ = cs.run_passes(
            str(tmp_path / "x"), ["export-drift"])
        assert not problems
        msgs = " | ".join(f.msg for f in kept)
        assert "ContinuousBatchingEngine" in msgs

    @pytest.mark.parametrize("src_name,old,new,needle,says", [
        # a name bound at import: the tests' patch would miss it again
        ("scheduler.py", "from ..framework import device as _device",
         "from ..framework.device import use_pallas_kernels",
         "import use_pallas_kernels", "bound by name"),
        # a second home for the predicate
        ("moe_serving.py", "def moe_capacity(",
         "def use_pallas_kernels():\n    return False\n\n\n"
         "def moe_capacity(",
         "def use_pallas_kernels", "second definition"),
        # the argument every caller passed the same value for
        ("paged_cache.py", "    def decode(self, q, k, v, t):",
         "    def decode(self, q, k, v, t, use_kernel=False):",
         "use_kernel=False", "PagedLayerCache.decode"),
    ])
    def test_kernel_seam_regressions(self, tmp_path, src_name, old, new,
                                     needle, says):
        """What PR 30 took out stays out: the predicate bound by name,
        defined a second time, or travelling as ``use_kernel`` into a
        paged view's ``decode`` flips exit 0 -> 1 at the line."""
        root, path = _mutate(tmp_path, src_name, old, new)
        kept, _ = run(root, ["kernel-seam"])
        assert (path, lineno(path, needle)) in \
            {(f.path, f.line) for f in kept}
        assert any(says in f.msg for f in kept)

    def test_net_transport_time_import_flips_red(self, tmp_path):
        """The session-transport determinism gate: net.py importing
        the clock module — under ANY alias — flips exit 0 -> 1 the
        moment the import lands, before a single clock read."""
        root, path = _mutate(
            tmp_path, "net.py",
            "import select as _select",
            "import select as _select\nimport time as _clock")
        kept, _ = run(root, ["net-clock-purity"])
        assert [(f.path, f.line) for f in kept] == \
            [(path, lineno(path, "import time as _clock"))]
        assert "imports time" in kept[0].msg

    def test_net_transport_clock_read_flips_red(self, tmp_path):
        """...and a wall-clock READ sneaking into the backoff path
        (the exact mutation that would silently break two-runs-
        recover-identically) is anchored at the call site."""
        root, path = _mutate(
            tmp_path, "net.py",
            "import select as _select",
            "import select as _select\nfrom time import monotonic")
        kept, _ = run(root, ["net-clock-purity"])
        assert kept and kept[0].line == \
            lineno(path, "from time import monotonic")
        assert "no clock symbols" in kept[0].msg


# =====================================================================
# CLI: exit codes, --json envelope, pass selection
# =====================================================================

class TestCLI:
    def test_exit_0_on_clean_tree(self, capsys):
        # the inference subtree (the full-tree gate is TestRealTree)
        assert cs.main([INF]) == 0
        out = capsys.readouterr().out
        assert "0 finding(s)" in out and "OK" in out

    def test_exit_1_on_findings(self, capsys):
        assert cs.main([os.path.join(FIX, "charge")]) == 1
        assert "charge-discipline" in capsys.readouterr().out

    def test_exit_2_on_missing_root(self, capsys):
        assert cs.main([os.path.join(FIX, "no_such_dir")]) == 2
        assert "UNREADABLE" in capsys.readouterr().out

    def test_exit_2_on_syntax_error(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("def broken(:\n")
        assert cs.main([str(tmp_path)]) == 2
        assert "unparseable" in capsys.readouterr().out

    def test_pass_selection(self):
        # the snapshot fixture is clean under every OTHER pass
        kept, supp = run(os.path.join(FIX, "snapshot"),
                         ["charge-discipline", "span-safety",
                          "hot-path-purity", "journal-coverage",
                          "export-drift", "compiled-step-purity",
                          "kernel-seam"])
        assert kept == [] and supp == []

    def test_list_passes(self, capsys):
        assert cs.main(["--list-passes"]) == 0
        out = capsys.readouterr().out
        for pid in cs.PASS_IDS:
            assert pid in out
        assert len(cs.PASS_IDS) == 9

    def test_json_envelope_clean(self, capsys):
        """--json speaks the shared paddle_tpu.report.v1 envelope
        (tools/_report.py) — same schema the other report doctors
        emit, so CI gates on this artifact identically."""
        from tools._report import SCHEMA
        assert cs.main([INF, "--json"]) == 0
        env = json.loads(capsys.readouterr().out)
        assert env["schema"] == SCHEMA
        assert env["tool"] == "check_static"
        assert env["ok"] is True and env["exit"] == 0
        assert env["problems"] == []
        assert env["data"]["findings"] == []
        assert env["data"]["files_scanned"] > 5
        assert set(env["data"]["passes"]) == set(cs.PASS_IDS)

    def test_json_envelope_findings(self, capsys):
        from tools._report import SCHEMA
        assert cs.main([os.path.join(FIX, "span"), "--json"]) == 1
        env = json.loads(capsys.readouterr().out)
        assert env["schema"] == SCHEMA and env["ok"] is False
        assert env["exit"] == 1
        assert len(env["data"]["findings"]) == 1
        f = env["data"]["findings"][0]
        assert set(f) == {"pass", "path", "line", "message"}
        assert f["pass"] == "span-safety"
        assert env["problems"]     # human-readable mirror
        # suppressed findings are reported, never silently dropped
        assert len(env["data"]["suppressed"]) == 1
