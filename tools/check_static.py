"""Contract linter: AST-based static enforcement of the serving
stack's correctness protocols (tools/check_static.py).

Thirteen PRs of review-hardening notes tell one story: the stack's
load-bearing contracts — zero-overhead observability hooks,
snapshot/restore field completeness, journal-replay record coverage,
tenant charge-site discipline, span balance — were enforced only
DYNAMICALLY (counting-clock tests, deep audits, seeded storms), so
every new field or record kind was a latent drift bug until a storm
happened to catch it. This tool makes those invariants checkable
mechanically, the way GSPMD-style systems survive scale: a small
multi-pass framework over ``paddle_tpu/``'s ASTs, each pass encoding
one contract the repo's history shows has bitten before.

Passes (ids are stable — they are the suppression/selection keys):

  snapshot-completeness  every mutable ``self.<attr>`` of a class
                         defining snapshot()/restore() must be read by
                         snapshot() (directly or via same-class
                         helpers) unless allowlisted as derived; every
                         key snapshot() serializes (top level + the
                         config/geometry/counters sections) must be
                         consumed by restore(); the Router leg checks
                         every _RouterReq field is rebuilt by
                         Router.recover.
  hot-path-purity        inside engine/cache hot paths, no time.*
                         clock reads and no deep touches of
                         collector/monitor/ledger/registry/injector
                         unless dominated by an ``is not None`` hook
                         guard (the statically-checked twin of the
                         counting-clock tests).
  journal-coverage       every journal record kind a file writes has a
                         ``kind == "..."`` replay handler in that same
                         file, and every RequestOutcome member is
                         named at the router's delivery switch.
  charge-discipline      every function that mutates a slot's
                         ``seq_blocks`` table reaches ``_charge`` (the
                         tenant billing gauge cannot silently rot when
                         a new lifecycle op lands).
  span-safety            every ``span_begin`` in engine code is closed
                         on all paths — try/finally, an unwinding
                         except that re-raises, or the enclosing
                         function is itself bracketed by such a try.
  export-drift           names in ``inference/__init__.py``'s
                         ``__all__`` (and its ``from . import``s) must
                         exist; public ``*Engine``/``*Stats`` classes
                         defined in the package must be exported.

  kernel-seam            ``use_pallas_kernels`` (kernel or jnp
                         fallback) is defined in framework/device.py
                         alone and never bound by name at import;
                         ``decode`` of an ``is_paged`` view takes no
                         ``use_kernel`` parameter.

Suppression: append ``# lint: ok(<pass-id>)`` to the flagged line (or
the line directly above it); several ids may be comma-separated.
Suppressed findings are counted and reported, never silently dropped.

Usage:
  python tools/check_static.py [paddle_tpu] [--pass ID ...] [--json]
  python tools/check_static.py --list-passes

Exit status (the other doctors' convention): 0 no unsuppressed
findings, 1 findings, 2 unreadable input (missing root / syntax
error). ``--json`` emits the shared ``paddle_tpu.report.v1`` envelope
(tools/_report.py), so CI gates on this artifact exactly like
trace_report/health_report/cost_report ones.
"""
from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from typing import Dict, List, Optional, Set, Tuple

try:
    from tools._report import envelope, emit_json
except ImportError:      # run as a script: tools/ is sys.path[0]
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools._report import envelope, emit_json


# =====================================================================
# shared AST utilities
# =====================================================================

def chain_of(node) -> Optional[str]:
    """Dotted chain of an attribute/name expression — ``self.cache``,
    ``col.span_begin`` — or None for anything more exotic."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_chain(call: ast.Call) -> Optional[str]:
    return chain_of(call.func)


def str_constants(node) -> Set[str]:
    return {n.value for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


def self_attr_stores(func: ast.AST, inst: str = "self") -> Dict[str, int]:
    """{attr: first line} for every ``<inst>.X = / += / : T =`` in
    ``func`` — including attributes bound through tuple/list
    unpacking (``self.a, self.b = ...``) — but not subscripts."""
    out: Dict[str, int] = {}
    for n in ast.walk(func):
        targets = []
        if isinstance(n, ast.Assign):
            targets = list(n.targets)
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
            targets = [n.target]
        while targets:
            t = targets.pop()
            if isinstance(t, (ast.Tuple, ast.List)):
                targets += list(t.elts)
            elif isinstance(t, ast.Starred):
                targets.append(t.value)
            elif isinstance(t, ast.Attribute) and \
                    isinstance(t.value, ast.Name) and \
                    t.value.id == inst:
                out[t.attr] = min(out.get(t.attr, t.lineno), t.lineno)
    return out


def attr_loads(func: ast.AST, inst: str = "self") -> Set[str]:
    """Names X such that ``<inst>.X`` is loaded anywhere in func."""
    return {n.attr for n in ast.walk(func)
            if isinstance(n, ast.Attribute)
            and isinstance(n.value, ast.Name) and n.value.id == inst}


def methods_of(cls: ast.ClassDef) -> Dict[str, ast.AST]:
    return {n.name: n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def self_calls(func: ast.AST) -> Set[str]:
    """Names of same-instance methods called: self.m(...) or cls.m(...)."""
    out = set()
    for n in ast.walk(func):
        if isinstance(n, ast.Call):
            c = call_chain(n)
            if c and c.count(".") == 1 and \
                    c.split(".")[0] in ("self", "cls"):
                out.add(c.split(".")[1])
    return out


def is_none_test(test) -> List[str]:
    """Chains guarded by this test: ``X is not None`` (also every
    conjunct of an ``and``). An ``or`` of tests guards nothing on its
    own — either side may be None inside the body."""
    out: List[str] = []
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        for v in test.values:
            out += is_none_test(v)
    elif isinstance(test, ast.Compare) and len(test.ops) == 1 and \
            isinstance(test.ops[0], ast.IsNot) and \
            isinstance(test.comparators[0], ast.Constant) and \
            test.comparators[0].value is None:
        c = chain_of(test.left)
        if c:
            out.append(c)
    return out


def has_none_compare(test) -> bool:
    """Whether the test involves ANY ``is None`` / ``is not None``
    comparison (the opt-in-conditional shape clock reads may hide
    behind)."""
    for n in ast.walk(test):
        if isinstance(n, ast.Compare) and any(
                isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops):
            if any(isinstance(c, ast.Constant) and c.value is None
                   for c in n.comparators):
                return True
    return False


def terminates(stmts: List[ast.stmt]) -> bool:
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break))


class SourceFile:
    def __init__(self, path: str, rel: str, tree: ast.Module,
                 lines: List[str]):
        self.path = path          # as reported in findings
        self.rel = rel
        self.base = os.path.basename(path)
        self.tree = tree
        self.lines = lines

    def classes(self) -> List[ast.ClassDef]:
        return [n for n in ast.walk(self.tree)
                if isinstance(n, ast.ClassDef)]


class Finding:
    def __init__(self, pass_id: str, path: str, line: int, msg: str):
        self.pass_id = pass_id
        self.path = path
        self.line = int(line)
        self.msg = msg

    def key(self):
        return (self.path, self.line, self.pass_id, self.msg)

    def __repr__(self):
        return f"{self.path}:{self.line} [{self.pass_id}] {self.msg}"

    def as_dict(self):
        return {"pass": self.pass_id, "path": self.path,
                "line": self.line, "message": self.msg}


# =====================================================================
# pass 1: snapshot-completeness
# =====================================================================

# Mutable state that deliberately does NOT round-trip a snapshot —
# each entry records WHY (derived/observational), so the allowlist is
# reviewable instead of being a silent hole. A new field lands here
# only with a reason.
SNAPSHOT_ATTR_ALLOW: Dict[str, Dict[str, str]] = {
    "PagedKVCache": {
        "shard_devices": "runtime placement, not state — device "
                         "handles are process-local and the restore "
                         "target's mesh supplies its own "
                         "(restore(shard_devices=...); the payload "
                         "is canonical full-head pages either way)",
        "_audit_fp": "content-audit memo — re-fingerprinted on demand",
        "views": "derived per-layer views over the live pool",
        "_bt_cached": "device block-table mirror — _tables_dirty()",
        "_bt_rows_cached": "device block-table mirror",
        "_decode_masked": "per-step mask — re-set by the next step",
        "block_tables": "derived from seq_blocks during restore",
        "_tenant_charge": "derived via _charge() during restore",
        "_written": "pages / rows moved since the last pool_write gauge "
                    "sample (PR 29): observational, reset by every "
                    "take_write_stats()",
    },
    "PagedServingEngine": {
        "model": "weights are the caller's problem (restore arg)",
        "collector": "observational — never snapshotted (PR 8)",
        "_collector": "the collector property's slot (PR 28): "
                      "observational — never snapshotted",
        "monitor": "derived control-plane state (PR 9)",
        "ledger": "accounting hook — replay-frozen, never snapshotted",
        "registry": "always-on metric surface — reattached on build",
        "injector": "fault schedules are wired fresh by the caller",
        "max_len": "derived from the restored cache geometry",
        "_ragged_plan": "per-step launch plan — built and flushed "
                        "inside one step, empty at every snapshot "
                        "boundary",
        "_queue_len": "O(1) depth gauge — recomputed from the "
                      "sub-queues on restore (audited by "
                      "check_invariants)",
        "_next_enqueue_seq": "enqueue seqs are reassigned "
                             "monotonically on restore; only their "
                             "relative order (the saved queue list) "
                             "is behavioral",
    },
    "SpeculativeEngine": {
        "injector": "fault schedules are wired fresh by the caller",
        "_seqs": "slot->seq map — derived from _by_rid[*].slot",
        "_draft_lens": "derived — draft rebuild recomputes them",
        "max_batch": "restored from the wrapped engine's config "
                     "section (single source of truth)",
    },
    "MoeServingCore": {
        "_ep_devices": "runtime placement, not state — device handles "
                       "are process-local; restore() re-derives them "
                       "by re-running shard_experts(ep) off the "
                       "snapshot's config.ep",
        "_ep_weights": "derived per-shard views: device_put slices of "
                       "the stacked expert Parameters (which ride "
                       "state_dict like any weight) — rebuilt by "
                       "shard_experts during restore",
    },
    "FleetSupervisor": {
        "router": "live wiring — restore() takes the (recovered) "
                  "router as an argument, it is not serializable "
                  "state",
        "registry": "live wiring — gauges are attach()ed closures "
                    "over the router; a restored supervisor "
                    "re-attaches to a fresh/supplied registry",
        "monitor": "live wiring — monitor state is DERIVED, never "
                   "snapshotted (the recovery contract monitor.py "
                   "documents); restore() rebinds a supplied one",
        "_checkpoints": "in-memory page archive — re-seeded from the "
                        "next full checkpoint after a restore (the "
                        "workers' own snapshot files are the durable "
                        "copy; byte counters DO round-trip)",
    },
}

# Snapshot keys consumed by tooling rather than restore().
SNAPSHOT_KEY_ALLOW: Set[str] = {"kind"}

# Nested sections whose keys are checked individually (a new config
# knob MUST be consumed by restore); other nested dicts may be
# consumed wholesale (e.g. ``dict(st)``) and are not key-checked.
SNAPSHOT_KEY_SECTIONS = ("config", "geometry", "counters")

# The Router has no snapshot(): its durable state is the journal, and
# ``Router.recover`` rebuilds the request table. Fields reset by
# design are allowlisted with reasons.
ROUTER_RECOVER = {
    "router_class": "Router",
    "recover_method": "recover",
    "req_class": "_RouterReq",
    "allow": {
        "worker": "placement is per-incarnation — re-placed on step()",
        "wrid": "worker-side rid dies with the dead fleet wiring",
        "resubmissions": "worker-failure retry budget is "
                         "per-incarnation by design",
    },
}


class SnapshotCompleteness:
    id = "snapshot-completeness"
    doc = ("snapshot()/restore() round-trip every mutable field; "
           "Router.recover rebuilds every _RouterReq field")

    def _expand_reads(self, cls: ast.ClassDef, entry: str,
                      depth: int = 4) -> Set[str]:
        """Attr loads reachable from ``entry`` through same-class
        helper calls (bounded depth)."""
        meths = methods_of(cls)
        seen: Set[str] = set()
        frontier = [entry]
        reads: Set[str] = set()
        while frontier and depth > 0:
            depth -= 1
            nxt = []
            for name in frontier:
                if name in seen or name not in meths:
                    continue
                seen.add(name)
                reads |= attr_loads(meths[name], "self")
                nxt += list(self_calls(meths[name]))
            frontier = nxt
        return reads

    def _collect_dict(self, d: ast.Dict, out: Dict[str, int],
                      dict_vars: Optional[Dict[str, ast.Dict]] = None,
                      ) -> None:
        for k, v in zip(d.keys, d.values):
            if k is None:
                # ``**({...} if cond else {})`` merge: the starred
                # expression's literal keys are top-level keys too
                for n in ast.walk(v):
                    if isinstance(n, ast.Dict):
                        for kk in n.keys:
                            if isinstance(kk, ast.Constant) and \
                                    isinstance(kk.value, str):
                                out.setdefault(kk.value, kk.lineno)
                continue
            if not (isinstance(k, ast.Constant)
                    and isinstance(k.value, str)):
                continue
            out.setdefault(k.value, k.lineno)
            # only the named sections are key-checked one level down:
            # a new config/geometry knob MUST be consumed by restore,
            # while other nested records may be consumed wholesale.
            # A section staged in a local (``geometry = {...}`` then
            # ``"geometry": geometry``) is followed to its literal —
            # snapshot() building the section early (e.g. to compare
            # against a delta base) must not vacate the key check.
            if k.value in SNAPSHOT_KEY_SECTIONS:
                if isinstance(v, ast.Name) and dict_vars and \
                        v.id in dict_vars:
                    v = dict_vars[v.id]
                if isinstance(v, ast.Dict):
                    for kk in v.keys:
                        if isinstance(kk, ast.Constant) and \
                                isinstance(kk.value, str):
                            out.setdefault(kk.value, kk.lineno)

    def _snapshot_keys(self, func: ast.AST) -> Dict[str, int]:
        """{key: line} for the snapshot RETURN dict's literal keys
        plus the keys of the checked nested sections. Handles both
        ``return {...}`` and the incremental shape ``d = {...};
        d["k"] = ...; return d`` so a refactor to staged assembly
        cannot silently vacate the check."""
        out: Dict[str, int] = {}
        dict_vars: Dict[str, ast.Dict] = {}
        sub_keys: Dict[str, Dict[str, int]] = {}
        for n in ast.walk(func):
            if isinstance(n, ast.Assign) and len(n.targets) == 1:
                t = n.targets[0]
                if isinstance(t, ast.Name) and \
                        isinstance(n.value, ast.Dict):
                    dict_vars[t.id] = n.value
                elif isinstance(t, ast.Subscript) and \
                        isinstance(t.value, ast.Name) and \
                        isinstance(t.slice, ast.Constant) and \
                        isinstance(t.slice.value, str):
                    sub_keys.setdefault(t.value.id, {}).setdefault(
                        t.slice.value, t.lineno)
        for n in ast.walk(func):
            if not isinstance(n, ast.Return) or n.value is None:
                continue
            if isinstance(n.value, ast.Dict):
                self._collect_dict(n.value, out, dict_vars)
            elif isinstance(n.value, ast.Name):
                name = n.value.id
                if name in dict_vars:
                    self._collect_dict(dict_vars[name], out,
                                       dict_vars)
                for k, ln in sub_keys.get(name, {}).items():
                    out.setdefault(k, ln)
        return out

    def _check_class(self, sf: SourceFile, cls: ast.ClassDef,
                     findings: List[Finding]) -> None:
        meths = methods_of(cls)
        snap, rest = meths.get("snapshot"), meths.get("restore")
        if snap is None or rest is None:
            return
        allow = SNAPSHOT_ATTR_ALLOW.get(cls.name, {})
        # (a) every mutable attr is read by snapshot (or allowlisted)
        mut: Dict[str, int] = {}
        for m in meths.values():
            for a, ln in self_attr_stores(m, "self").items():
                mut.setdefault(a, ln)
        reads = self._expand_reads(cls, "snapshot")
        for attr in sorted(mut):
            if attr in reads or attr in allow:
                continue
            findings.append(Finding(
                self.id, sf.path, mut[attr],
                f"{cls.name}.{attr} is mutable state but is never "
                f"read by {cls.name}.snapshot() — it will not "
                f"round-trip a crash (serialize it, or allowlist it "
                f"with a reason in SNAPSHOT_ATTR_ALLOW)"))
        # (b) every serialized key is consumed by restore
        keys = self._snapshot_keys(snap)
        consumed = str_constants(rest)
        for key in sorted(keys):
            if key in consumed or key in SNAPSHOT_KEY_ALLOW:
                continue
            findings.append(Finding(
                self.id, sf.path, keys[key],
                f"snapshot key {key!r} of {cls.name}.snapshot() is "
                f"never consumed by {cls.name}.restore() — the field "
                f"is serialized but silently dropped on recovery"))

    def _check_router(self, files: List[SourceFile],
                      findings: List[Finding]) -> None:
        cfg = ROUTER_RECOVER
        for sf in files:
            by_name = {c.name: c for c in sf.classes()}
            rc = by_name.get(cfg["router_class"])
            qc = by_name.get(cfg["req_class"])
            if rc is None or qc is None:
                continue
            recover = methods_of(rc).get(cfg["recover_method"])
            if recover is None:
                continue
            init = methods_of(qc).get("__init__")
            if init is None:
                continue
            fields = self_attr_stores(init, "self")
            # locals that hold request-record instances: assigned from
            # a <req_class>(...) call, pulled out of a ``_reqs``
            # table, or iterating one — ONLY their attributes count
            # as rebuilt (an unrelated object happening to share a
            # field's name, e.g. ``router.tick`` vs a future
            # ``_RouterReq.tick``, must not mask the finding)
            req_vars: Set[str] = set()
            for n in ast.walk(recover):
                src = None
                if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                        and isinstance(n.targets[0], ast.Name):
                    src, tgt = n.value, n.targets[0].id
                elif isinstance(n, ast.For) and \
                        isinstance(n.target, ast.Name):
                    src, tgt = n.iter, n.target.id
                if src is None:
                    continue
                c = call_chain(src) if isinstance(src, ast.Call) \
                    else chain_of(src)
                if c and (c.split(".")[-1] == cfg["req_class"]
                          or "_reqs" in c.split(".")):
                    req_vars.add(tgt)
            touched: Set[str] = set()
            for n in ast.walk(recover):
                if isinstance(n, ast.Attribute) and \
                        isinstance(n.value, ast.Name) and \
                        n.value.id in req_vars:
                    touched.add(n.attr)
                if isinstance(n, ast.Call):
                    c = call_chain(n)
                    if c and c.split(".")[-1] == cfg["req_class"]:
                        touched |= {kw.arg for kw in n.keywords
                                    if kw.arg}
                        # positional args cover the leading params
                        params = [a.arg for a in init.args.args[1:]]
                        touched |= set(params[:len(n.args)])
            for f in sorted(fields):
                if f in touched or f in cfg["allow"]:
                    continue
                findings.append(Finding(
                    self.id, sf.path, fields[f],
                    f"{cfg['req_class']}.{f} is never rebuilt by "
                    f"{cfg['router_class']}.{cfg['recover_method']}() "
                    f"— a recovered router silently resets it "
                    f"(journal it, rebuild it, or allowlist it with "
                    f"a reason)"))

    def run(self, files: List[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files:
            for cls in sf.classes():
                self._check_class(sf, cls, findings)
        self._check_router(files, findings)
        return findings


# =====================================================================
# pass 2: hot-path-purity
# =====================================================================

HOOK_ROOTS = ("collector", "monitor", "ledger", "registry", "injector")
HOOK_ALIASES = {"col": "collector", "mon": "monitor", "led": "ledger",
                "inj": "injector", "collector": "collector",
                "monitor": "monitor", "ledger": "ledger",
                "registry": "registry", "injector": "injector"}
CLOCK_CALLS = {"time", "monotonic", "perf_counter", "process_time",
               "thread_time", "clock_gettime", "monotonic_ns",
               "perf_counter_ns", "time_ns"}

# Hot classes and their COLD methods (admin/recovery/diagnostic
# surfaces that may touch hooks or clocks unconditionally). A method
# not listed cold is hot by default: new engine code inherits the
# zero-overhead contract until someone consciously declares it cold.
HOT_CLASSES: Dict[str, Set[str]] = {
    "PagedServingEngine": {"__init__", "snapshot", "restore",
                           "check_invariants", "set_tenant",
                           "tenant_report", "tenant_stats",
                           "_stats_rec", "_stats_set", "_req_rec",
                           "export_request_slice", "import_slice"},
    "SpeculativeEngine": {"__init__", "snapshot", "restore",
                          "check_invariants",
                          "export_request_slice", "import_slice"},
    "RecoverableServer": {"__init__", "recover", "save_snapshot",
                          "close", "check_invariants",
                          "export_slice", "import_slice",
                          "set_tenant"},
    "PagedKVCache": {"__init__", "snapshot", "restore",
                     "check_invariants", "pool_occupancy",
                     "_pool_context", "_describe_block", "for_model",
                     "export_slice", "import_slice"},
    "PagedLayerCache": set(),
    "PagedPrefillView": set(),
    "PagedRaggedView": set(),
    "_RaggedLayout": set(),
    "BlockAllocator": set(),
    # the tensor-parallel serving core sits inside every sharded model
    # call (one visit per layer per shard): hot throughout — only
    # construction (weight slicing/placement) is cold
    "ShardedServingCore": {"__init__"},
    # the MoE serving core's routing/dispatch/combine runs inside every
    # model call (per layer): hot by default — construction, expert
    # sharding and the snapshot/metrics scrapes are the cold admin
    # surface (moe_metrics is the registry's attach() target, pulled
    # only when a cold consumer scrapes the registry)
    "MoeServingCore": {"__init__", "snapshot", "restore",
                       "shard_experts", "moe_metrics", "truncated",
                       "moe_spec"},
}

# Files whose MODULE-LEVEL functions are hot (kernel launch paths).
HOT_FILES = {"paged_attention.py"}


def clock_aliases(tree: ast.Module) -> Tuple[Set[str], Set[str]]:
    """(module aliases, bare function aliases) under which this file
    can reach the clock: ``import time [as t]`` and ``from time
    import monotonic [as m]`` — so aliased imports cannot slip a
    clock read past the purity pass."""
    mods = {"time", "_time"}
    funcs: Set[str] = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            for a in n.names:
                if a.name == "time":
                    mods.add(a.asname or a.name)
        elif isinstance(n, ast.ImportFrom) and n.module == "time":
            for a in n.names:
                if a.name in CLOCK_CALLS:
                    funcs.add(a.asname or a.name)
    return mods, funcs


class _PurityVisitor(ast.NodeVisitor):
    """Walks one hot function carrying the set of guarded chains."""

    def __init__(self, lint, sf, fname, clocks=None):
        self.lint = lint
        self.sf = sf
        self.fname = fname
        self.clock_mods, self.clock_funcs = \
            clocks if clocks is not None else ({"time", "_time"},
                                               set())
        self.guards: Set[str] = set()
        self.none_cond_depth = 0     # inside ANY is-None conditional
        self.aliases: Dict[str, str] = dict(HOOK_ALIASES)
        self.findings: List[Finding] = []

    # -- helpers ------------------------------------------------------
    def _hook_root(self, chain: str) -> Optional[str]:
        """Longest prefix of ``chain`` that IS a hook object, or
        None. ``self.collector.on_submit`` -> ``self.collector``;
        ``col.span_begin`` -> ``col``."""
        parts = chain.split(".")
        for i in range(len(parts), 0, -1):
            prefix = parts[:i]
            last = prefix[-1]
            if last in HOOK_ROOTS or \
                    self.aliases.get(last) in HOOK_ROOTS:
                return ".".join(prefix)
        return None

    def _flag(self, node, msg):
        self.findings.append(Finding(
            self.lint.id, self.sf.path, node.lineno, msg))

    def _check_expr(self, node):
        """Flag unguarded deep hook touches / clock reads in an
        expression subtree, honoring nested IfExp guards."""
        if isinstance(node, ast.IfExp):
            new = is_none_test(node.test)
            saved, saved_d = set(self.guards), self.none_cond_depth
            self.guards |= set(new)
            self.none_cond_depth += has_none_compare(node.test)
            self._check_expr(node.body)
            self.guards, self.none_cond_depth = saved, saved_d
            self._check_expr(node.test)
            self._check_expr(node.orelse)
            return
        if isinstance(node, ast.BoolOp) and isinstance(node.op, ast.And):
            # left conjuncts guard the right ones
            saved, saved_d = set(self.guards), self.none_cond_depth
            for v in node.values:
                self._check_expr(v)
                self.guards |= set(is_none_test(v))
                self.none_cond_depth += has_none_compare(v)
            self.guards, self.none_cond_depth = saved, saved_d
            return
        if isinstance(node, ast.Call):
            c = call_chain(node)
            if c:
                parts = c.split(".")
                is_clock = (
                    (len(parts) == 2 and parts[0] in self.clock_mods
                     and parts[1] in CLOCK_CALLS)
                    or (len(parts) == 1
                        and parts[0] in self.clock_funcs))
                if is_clock:
                    if self.none_cond_depth == 0:
                        self._flag(node, (
                            f"unconditional clock read {c}() on hot "
                            f"path {self.fname} — wall-clock must be "
                            f"opt-in (guard it behind an "
                            f"``is not None`` conditional)"))
                    for a in list(node.args) + \
                            [kw.value for kw in node.keywords]:
                        self._check_expr(a)
                    return
            # fall through to attribute check on func + args
        if isinstance(node, ast.Attribute):
            c = chain_of(node)
            if c:
                root = self._hook_root(c)
                if root is not None and c != root:
                    # deep touch: attribute/call past the hook object
                    if root not in self.guards:
                        kind = root.split(".")[-1]
                        kind = self.aliases.get(kind, kind)
                        self._flag(node, (
                            f"hot path {self.fname} touches "
                            f"{c} without an ``if "
                            f"{root} is not None`` guard — the "
                            f"zero-overhead-when-off contract "
                            f"(hook: {kind})"))
                    return       # chain checked as a unit
        for ch in ast.iter_child_nodes(node):
            self._check_expr(ch)

    # -- statement walking --------------------------------------------
    def _walk_block(self, stmts: List[ast.stmt]):
        extra: Set[str] = set()
        for st in stmts:
            saved = set(self.guards)
            self.guards |= extra
            self._walk_stmt(st)
            # ``if X is None: return/raise`` guards the remainder
            if isinstance(st, ast.If) and not st.orelse and \
                    terminates(st.body):
                t = st.test
                if isinstance(t, ast.Compare) and len(t.ops) == 1 \
                        and isinstance(t.ops[0], ast.Is) \
                        and isinstance(t.comparators[0], ast.Constant) \
                        and t.comparators[0].value is None:
                    c = chain_of(t.left)
                    if c:
                        extra.add(c)
            self.guards = saved
        self.guards |= extra     # caller restores

    def _walk_stmt(self, st: ast.stmt):
        if isinstance(st, ast.If):
            new = set(is_none_test(st.test))
            d = has_none_compare(st.test)
            self._check_expr(st.test)
            saved, saved_d = set(self.guards), self.none_cond_depth
            self.guards |= new
            self.none_cond_depth += d
            self._walk_block(st.body)
            self.guards, self.none_cond_depth = saved, saved_d
            self._walk_block(st.orelse)
            return
        if isinstance(st, ast.Assign):
            # alias tracking: name = <chain ending in a hook attr>
            if len(st.targets) == 1 and \
                    isinstance(st.targets[0], ast.Name):
                c = chain_of(st.value)
                if c:
                    last = c.split(".")[-1]
                    if last in HOOK_ROOTS:
                        self.aliases[st.targets[0].id] = last
                        # the bare load that binds the alias is free
                        self._check_expr_skip_root(st.value)
                        return
            self._check_expr(st.value)
            for t in st.targets:
                self._check_expr(t)
            return
        if isinstance(st, (ast.For, ast.While)):
            if isinstance(st, ast.For):
                self._check_expr(st.iter)
            else:
                self._check_expr(st.test)
            # guards established by early-outs inside the body must
            # not leak into the orelse (it runs on normal exhaustion,
            # but the body's terminating-if analysis doesn't hold
            # across iterations)
            saved = set(self.guards)
            self._walk_block(st.body)
            self.guards = set(saved)
            self._walk_block(st.orelse)
            self.guards = saved
            return
        if isinstance(st, ast.Try):
            # each region starts from the PRE-try guard set: an
            # exception can jump from anywhere in the body into a
            # handler/finally, so guards established mid-body (e.g.
            # an ``if X is None: return`` early-out) do not hold there
            saved = set(self.guards)
            self._walk_block(st.body)
            for h in st.handlers:
                self.guards = set(saved)
                self._walk_block(h.body)
            self.guards = set(saved)
            self._walk_block(st.orelse)
            self.guards = set(saved)
            self._walk_block(st.finalbody)
            self.guards = saved
            return
        if isinstance(st, ast.With):
            for item in st.items:
                self._check_expr(item.context_expr)
            self._walk_block(st.body)
            return
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._walk_block(st.body)    # nested closure: same rules
            return
        for ch in ast.iter_child_nodes(st):
            if isinstance(ch, ast.expr):
                self._check_expr(ch)
            elif isinstance(ch, ast.stmt):
                self._walk_stmt(ch)

    def _check_expr_skip_root(self, node):
        """Check an alias-binding RHS, allowing the bare hook load
        itself (binding ``col = self.collector`` costs nothing)."""
        if isinstance(node, (ast.Attribute, ast.Name)):
            return
        self._check_expr(node)


class HotPathPurity:
    id = "hot-path-purity"
    doc = ("no clock reads or unguarded observability-hook touches "
           "inside engine/cache hot paths")

    def run(self, files: List[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files:
            clocks = clock_aliases(sf.tree)
            for cls in sf.classes():
                cold = HOT_CLASSES.get(cls.name)
                if cold is None:
                    continue
                for name, m in methods_of(cls).items():
                    if name in cold:
                        continue
                    v = _PurityVisitor(self, sf, f"{cls.name}.{name}",
                                       clocks)
                    v._walk_block(m.body)
                    findings += v.findings
            if sf.base in HOT_FILES:
                for n in sf.tree.body:
                    if isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                        v = _PurityVisitor(self, sf, n.name, clocks)
                        v._walk_block(n.body)
                        findings += v.findings
        return findings


# =====================================================================
# pass 3: journal-coverage
# =====================================================================

OUTCOME_SWITCH = {
    # every RequestOutcome member must be NAMED inside the router's
    # delivery switch FUNCTION — a reference elsewhere in router.py
    # (an assignment site, a placement path) does not count: a new
    # member must be consciously routed where worker verdicts are
    # dispatched, not silently absorbed by a catch-all branch
    "outcome_class": "RequestOutcome",
    "switch_basename": "router.py",
    "switch_function": "_worker_outcome",
}


class JournalCoverage:
    id = "journal-coverage"
    doc = ("every journal record kind written has a replay handler; "
           "every RequestOutcome member is named at the router's "
           "delivery switch")

    def _written_kinds(self, sf: SourceFile) -> Dict[str, int]:
        """{kind: line} of record kinds this file writes: literal
        first args of ``<...>journal.append(...)`` / ``_jrec(...)``
        calls, plus marker kinds framed directly via ``_frame((seq,
        "<kind>", ...))``."""
        out: Dict[str, int] = {}
        for n in ast.walk(sf.tree):
            if not isinstance(n, ast.Call):
                continue
            c = call_chain(n)
            if c is None:
                continue
            parts = c.split(".")
            is_append = (parts[-1] == "append" and len(parts) >= 2
                         and "journal" in parts[-2])
            is_jrec = parts[-1] == "_jrec"
            if (is_append or is_jrec) and n.args and \
                    isinstance(n.args[0], ast.Constant) and \
                    isinstance(n.args[0].value, str):
                out.setdefault(n.args[0].value, n.lineno)
            if parts[-1] == "_frame" and n.args and \
                    isinstance(n.args[0], ast.Tuple) and \
                    len(n.args[0].elts) >= 2:
                k = n.args[0].elts[1]
                if isinstance(k, ast.Constant) and \
                        isinstance(k.value, str):
                    out.setdefault(k.value, n.lineno)
        return out

    def _handled_kinds(self, sf: SourceFile) -> Set[str]:
        """Literals compared against a variable named ``kind``."""
        out: Set[str] = set()
        for n in ast.walk(sf.tree):
            if not isinstance(n, ast.Compare):
                continue
            sides = [n.left] + list(n.comparators)
            if not any(isinstance(s, ast.Name) and s.id == "kind"
                       for s in sides):
                continue
            for s in sides:
                if isinstance(s, ast.Constant) and \
                        isinstance(s.value, str):
                    out.add(s.value)
                elif isinstance(s, (ast.Tuple, ast.List, ast.Set)):
                    for e in s.elts:
                        if isinstance(e, ast.Constant) and \
                                isinstance(e.value, str):
                            out.add(e.value)
        return out

    def _outcome_members(self, files) -> Dict[str, Tuple[str, int]]:
        """{MEMBER: (path, line)} of the outcome class's string
        constants (STATUSES and dunders excluded)."""
        out: Dict[str, Tuple[str, int]] = {}
        for sf in files:
            for cls in sf.classes():
                if cls.name != OUTCOME_SWITCH["outcome_class"]:
                    continue
                for st in cls.body:
                    if isinstance(st, ast.Assign) and \
                            len(st.targets) == 1 and \
                            isinstance(st.targets[0], ast.Name) and \
                            st.targets[0].id.isupper() and \
                            isinstance(st.value, ast.Constant) and \
                            isinstance(st.value.value, str):
                        out[st.targets[0].id] = (sf.path, st.lineno)
        return out

    def run(self, files: List[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files:
            written = self._written_kinds(sf)
            if not written:
                continue
            handled = self._handled_kinds(sf)
            for kind in sorted(written):
                if kind in handled:
                    continue
                findings.append(Finding(
                    self.id, sf.path, written[kind],
                    f"journal record kind {kind!r} is written here "
                    f"but has no ``kind == {kind!r}`` replay handler "
                    f"in {sf.base} — replay will silently skip it"))
        # RequestOutcome members named at the router switch
        members = self._outcome_members(files)
        switches = [sf for sf in files
                    if sf.base == OUTCOME_SWITCH["switch_basename"]]
        if members and switches:
            ocls = OUTCOME_SWITCH["outcome_class"]
            swfn = OUTCOME_SWITCH["switch_function"]
            for sw in switches:
                scopes = [n for n in ast.walk(sw.tree)
                          if isinstance(n, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))
                          and n.name == swfn] or [sw.tree]
                named = {n.attr for scope in scopes
                         for n in ast.walk(scope)
                         if isinstance(n, ast.Attribute)
                         and isinstance(n.value, ast.Name)
                         and n.value.id == ocls}
                for m, (path, line) in sorted(members.items()):
                    if m in named:
                        continue
                    findings.append(Finding(
                        self.id, path, line,
                        f"{ocls}.{m} is never named in {sw.base}'s "
                        f"{swfn}() — the router's delivery switch "
                        f"does not consciously route this outcome"))
        return findings


# =====================================================================
# pass 4: charge-discipline
# =====================================================================

CHARGE_ALLOW: Dict[Tuple[str, str], str] = {
    ("PagedKVCache", "_copy_block"):
        "COW swap replaces one table entry in place — table length "
        "(and so the per-tenant charge) is unchanged",
}

_MUTATORS = {"append", "extend", "insert", "pop", "remove", "clear"}


class ChargeDiscipline:
    id = "charge-discipline"
    doc = ("every seq_blocks table mutation reaches _charge (tenant "
           "billing gauge)")

    def _table_aliases(self, func) -> Set[str]:
        """Local names bound to ``<inst>.seq_blocks[...]``."""
        out: Set[str] = set()
        for n in ast.walk(func):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                    isinstance(n.targets[0], ast.Name) and \
                    isinstance(n.value, ast.Subscript):
                c = chain_of(n.value.value)
                if c and c.split(".")[-1] == "seq_blocks":
                    out.add(n.targets[0].id)
        return out

    def _mutations(self, func) -> List[int]:
        """Lines where a slot table is mutated."""
        aliases = self._table_aliases(func)

        def is_table_sub(node) -> bool:
            if not isinstance(node, ast.Subscript):
                return False
            v = node.value
            if isinstance(v, ast.Name) and v.id in aliases:
                return True
            c = chain_of(v)
            if c and c.split(".")[-1] == "seq_blocks":
                return True
            # nested: self.seq_blocks[slot][bpos]
            if isinstance(v, ast.Subscript):
                cc = chain_of(v.value)
                return bool(cc and cc.split(".")[-1] == "seq_blocks")
            return False

        lines: List[int] = []
        for n in ast.walk(func):
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if is_table_sub(t):
                        lines.append(t.lineno)
            elif isinstance(n, ast.AugAssign) and is_table_sub(n.target):
                lines.append(n.target.lineno)
            elif isinstance(n, ast.Delete):
                for t in n.targets:
                    if is_table_sub(t):
                        lines.append(t.lineno)
            elif isinstance(n, ast.Call):
                f = n.func
                if isinstance(f, ast.Attribute) and \
                        f.attr in _MUTATORS and \
                        (is_table_sub(f.value) or
                         (isinstance(f.value, ast.Name)
                          and f.value.id in aliases)):
                    lines.append(n.lineno)
        return sorted(set(lines))

    def _reaches_charge(self, func) -> bool:
        for n in ast.walk(func):
            if isinstance(n, ast.Call):
                c = call_chain(n)
                if c and c.split(".")[-1] == "_charge":
                    return True
        return False

    def run(self, files: List[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files:
            for cls in sf.classes():
                for name, m in methods_of(cls).items():
                    muts = self._mutations(m)
                    if not muts:
                        continue
                    if (cls.name, name) in CHARGE_ALLOW:
                        continue
                    if self._reaches_charge(m):
                        continue
                    for ln in muts:
                        findings.append(Finding(
                            self.id, sf.path, ln,
                            f"{cls.name}.{name} mutates a slot's "
                            f"seq_blocks table but never calls "
                            f"_charge — the per-tenant billing gauge "
                            f"rots silently (charge, or allowlist "
                            f"with a reason in CHARGE_ALLOW)"))
        return findings


# =====================================================================
# pass 5: span-safety
# =====================================================================

SPAN_EXCLUDE_FILES = {"telemetry.py"}     # defines the span API


class SpanSafety:
    id = "span-safety"
    doc = ("every span_begin in engine code is closed on all paths "
           "(try/finally or an unwinding except that re-raises)")

    @staticmethod
    def _closing_calls(stmts) -> bool:
        for n in ast.walk(ast.Module(body=list(stmts),
                                     type_ignores=[])):
            if isinstance(n, ast.Call):
                c = call_chain(n)
                if c and c.split(".")[-1] in ("span_end",
                                              "span_unwind"):
                    return True
        return False

    def _protecting_tries(self, func) -> List[ast.Try]:
        out = []
        for n in ast.walk(func):
            if not isinstance(n, ast.Try):
                continue
            if n.finalbody and self._closing_calls(n.finalbody):
                out.append(n)
                continue
            for h in n.handlers:
                broad = h.type is None or (
                    isinstance(h.type, ast.Name)
                    and h.type.id in ("BaseException", "Exception"))
                reraises = any(isinstance(x, ast.Raise)
                               for x in ast.walk(ast.Module(
                                   body=list(h.body), type_ignores=[])))
                if broad and reraises and self._closing_calls(h.body):
                    out.append(n)
                    break
        return out

    @staticmethod
    def _stmt_before(func, target: ast.stmt) -> Optional[ast.stmt]:
        """The statement immediately preceding ``target`` in its
        enclosing block, or None."""
        for n in ast.walk(func):
            for field in ("body", "orelse", "finalbody"):
                block = getattr(n, field, None)
                if isinstance(block, list) and target in block:
                    i = block.index(target)
                    return block[i - 1] if i > 0 else None
            for h in getattr(n, "handlers", []):
                if target in h.body:
                    i = h.body.index(target)
                    return h.body[i - 1] if i > 0 else None
        return None

    @staticmethod
    def _count(func, names) -> int:
        k = 0
        for n in ast.walk(func):
            if isinstance(n, ast.Call):
                c = call_chain(n)
                if c and c.split(".")[-1] in names:
                    k += 1
        return k

    def run(self, files: List[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files:
            if sf.base in SPAN_EXCLUDE_FILES:
                continue
            funcs = [n for n in ast.walk(sf.tree)
                     if isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))]
            # functions bracketed by a protecting try at a call site
            protected_callees: Set[str] = set()
            for f in funcs:
                for t in self._protecting_tries(f):
                    for n in ast.walk(ast.Module(body=list(t.body),
                                                 type_ignores=[])):
                        if isinstance(n, ast.Call):
                            c = call_chain(n)
                            if c:
                                protected_callees.add(
                                    c.split(".")[-1])
            for f in funcs:
                begins = [n for n in ast.walk(f)
                          if isinstance(n, ast.Call)
                          and call_chain(n)
                          and call_chain(n).split(".")[-1]
                          == "span_begin"]
                if not begins:
                    continue
                tries = self._protecting_tries(f)
                balanced = self._count(
                    f, ("span_end", "span_unwind")) >= len(begins)
                caller_safe = f.name in protected_callees and balanced
                # a try protects begins inside its body, and begins in
                # the statement IMMEDIATELY before it (the ``if col:
                # span_begin`` opener) — not arbitrary earlier code,
                # or an unrelated later bracket would mask a leak
                spans_of: Dict[int, List[Tuple[int, int]]] = {}
                for t in tries:
                    rngs = [(t.body[0].lineno,
                             t.body[-1].end_lineno or t.lineno)]
                    prev = self._stmt_before(f, t)
                    if prev is not None:
                        rngs.append((prev.lineno,
                                     prev.end_lineno or prev.lineno))
                    spans_of[id(t)] = rngs
                for b in begins:
                    ok = caller_safe
                    for t in tries:
                        if any(lo <= b.lineno <= hi
                               for lo, hi in spans_of[id(t)]):
                            ok = True
                            break
                    if not ok:
                        findings.append(Finding(
                            self.id, sf.path, b.lineno,
                            f"span_begin in {f.name} is not closed "
                            f"on all paths — wrap it in try/finally "
                            f"(or an unwinding except that "
                            f"re-raises), or the span stack skews "
                            f"after the first mid-span exception"))
        return findings


# =====================================================================
# pass 6: export-drift
# =====================================================================

EXPORT_PACKAGE_DIRS = {"inference"}
EXPORT_SUFFIXES = ("Engine", "Stats")


class ExportDrift:
    id = "export-drift"
    doc = ("__all__ names exist; imported names exist in their source "
           "modules; public *Engine/*Stats classes are exported")

    @staticmethod
    def _top_level_defs(tree: ast.Module) -> Set[str]:
        out: Set[str] = set()
        for n in tree.body:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                out.add(n.name)
            elif isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
                    elif isinstance(t, (ast.Tuple, ast.List)):
                        out |= {e.id for e in t.elts
                                if isinstance(e, ast.Name)}
            elif isinstance(n, ast.AnnAssign) and \
                    isinstance(n.target, ast.Name):
                out.add(n.target.id)
            elif isinstance(n, (ast.Import, ast.ImportFrom)):
                for a in n.names:
                    out.add(a.asname or a.name.split(".")[0]
                            if isinstance(n, ast.Import)
                            else (a.asname or a.name))
            elif isinstance(n, (ast.If, ast.Try)):
                # a conditional/fallback import binds in ANY branch —
                # body, else, or an except handler (`try: from ._fast
                # import X / except ImportError: X = _slow`)
                blocks = [list(n.body), list(getattr(n, "orelse", [])),
                          list(getattr(n, "finalbody", []))]
                blocks += [list(h.body)
                           for h in getattr(n, "handlers", [])]
                for blk in blocks:
                    if blk:
                        out |= ExportDrift._top_level_defs(
                            ast.Module(body=blk, type_ignores=[]))
        return out

    def run(self, files: List[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        by_dir: Dict[str, Dict[str, SourceFile]] = {}
        for sf in files:
            d = os.path.dirname(sf.path)
            by_dir.setdefault(d, {})[sf.base] = sf
        for d, mods in by_dir.items():
            if os.path.basename(d) not in EXPORT_PACKAGE_DIRS:
                continue
            init = mods.get("__init__.py")
            if init is None:
                continue
            bound = self._top_level_defs(init.tree)
            # __all__ entries must resolve
            all_node = None
            for n in init.tree.body:
                if isinstance(n, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == "__all__"
                        for t in n.targets):
                    all_node = n.value
            exported: Set[str] = set()
            if all_node is not None and \
                    isinstance(all_node, (ast.List, ast.Tuple)):
                for e in all_node.elts:
                    if isinstance(e, ast.Constant) and \
                            isinstance(e.value, str):
                        exported.add(e.value)
                        if e.value not in bound:
                            findings.append(Finding(
                                self.id, init.path, e.lineno,
                                f"__all__ lists {e.value!r} but no "
                                f"such name is defined or imported "
                                f"in {init.base}"))
            # relative imports must resolve in their source modules
            for n in init.tree.body:
                if isinstance(n, ast.ImportFrom) and n.level == 1 \
                        and n.module:
                    src = mods.get(n.module + ".py")
                    if src is None:
                        continue
                    defs = self._top_level_defs(src.tree)
                    for a in n.names:
                        if a.name != "*" and a.name not in defs:
                            findings.append(Finding(
                                self.id, init.path, n.lineno,
                                f"from .{n.module} import {a.name}: "
                                f"{a.name!r} is not defined at the "
                                f"top level of {src.base}"))
            # public Engine/Stats classes must be exported
            for base, sf in mods.items():
                if base == "__init__.py":
                    continue
                for cls in sf.tree.body:
                    if isinstance(cls, ast.ClassDef) and \
                            not cls.name.startswith("_") and \
                            cls.name.endswith(EXPORT_SUFFIXES) and \
                            cls.name not in exported:
                        findings.append(Finding(
                            self.id, sf.path, cls.lineno,
                            f"public class {cls.name} "
                            f"({base}) is not exported in "
                            f"{init.base}.__all__ — engine/stats "
                            f"siblings are part of the API surface"))
        return findings


# =====================================================================
# pass 7: compiled-step-purity
# =====================================================================

# The compiled sharded step's contract (inference/compiled_step.py
# module docstring): nothing on the per-step call path may pull
# device data to host or hop devices — the whole point of the one-
# jitted-program design is that pools and activations stay resident.
# Host metadata flows IN via jnp.asarray (allowed); placement happens
# once at setup (allowlisted); snapshot/export/slice readback lives
# in paged_cache.py outside this scope. A violation that slips in
# silently re-serializes every step on the host — exactly the
# regression PR 15's 0.443x ratio measured — so it is a lint error,
# not a code-review nicety.

# every function in compiled_step.py is hot except the setup boundary
COMPILED_STEP_FILE = "compiled_step.py"
COMPILED_SETUP_ALLOW = {"__init__", "_setup_weights"}
# the per-step call path in serving.py that hands off to the runner
COMPILED_SERVING_SCOPE = {
    "classes": {"ShardedServingCore": {"forward", "__call__",
                                       "_allreduce"}},
    "functions": {"_uncommitted"},
}
# host hops by exact dotted chain (numpy pulls) ...
_HOST_HOP_EXACT = {"np.asarray", "numpy.asarray", "np.array",
                   "numpy.array"}
# ... and by chain tail (method/function spellings that force a
# device sync or transfer whatever the receiver is called)
_HOST_HOP_LAST = {"device_put", "device_get", "block_until_ready",
                  "copy_to_host_async", "item", "tolist"}


class CompiledStepPurity:
    id = "compiled-step-purity"
    doc = ("no host pulls (np.asarray/.item/.tolist/device_get) or "
           "device hops (device_put) on the compiled sharded step's "
           "per-step call path; setup boundaries allowlisted")

    def _scan(self, sf: SourceFile, fname: str,
              fn) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            c = call_chain(node)
            if not c:
                continue
            last = c.split(".")[-1]
            if c in _HOST_HOP_EXACT or last in _HOST_HOP_LAST:
                out.append(Finding(
                    self.id, sf.path, node.lineno,
                    f"{c}() on the compiled-step hot path {fname} — "
                    f"per-step code must stay device-resident (host "
                    f"metadata feeds IN via jnp.asarray; placement "
                    f"belongs in setup; readback belongs at "
                    f"snapshot/export/slice boundaries)"))
        return out

    def run(self, files: List[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files:
            if sf.base == COMPILED_STEP_FILE:
                for n in sf.tree.body:
                    if isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                        findings += self._scan(sf, n.name, n)
                for cls in sf.classes():
                    for name, m in methods_of(cls).items():
                        if name in COMPILED_SETUP_ALLOW:
                            continue
                        findings += self._scan(
                            sf, f"{cls.name}.{name}", m)
            elif sf.base == "serving.py":
                scope = COMPILED_SERVING_SCOPE
                for n in sf.tree.body:
                    if isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)) and \
                            n.name in scope["functions"]:
                        findings += self._scan(sf, n.name, n)
                for cls in sf.classes():
                    hot = scope["classes"].get(cls.name)
                    if not hot:
                        continue
                    for name, m in methods_of(cls).items():
                        if name in hot:
                            findings += self._scan(
                                sf, f"{cls.name}.{name}", m)
        return findings


# =====================================================================
# pass 8: net-clock-purity
# =====================================================================

# Files holding the session transport's retry/backoff machinery: the
# determinism contract (two seeded storms recover identically) forbids
# ANY wall-clock read — deadlines are slice counts, backoff is keyed
# by attempt index, waits ride select.select. The file must not even
# import time (the monitor module's discipline, enforced).
NET_CLOCK_FILES = {"net.py"}


class NetClockPurity:
    id = "net-clock-purity"
    doc = ("no wall-clock reads anywhere in the session transport "
           "(inference/net.py): no time import under any alias, no "
           "clock calls — retry/backoff schedules must be keyed to "
           "op seqs and attempt indices, never to a clock")

    def run(self, files: List[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files:
            if sf.base not in NET_CLOCK_FILES:
                continue
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Import):
                    for a in node.names:
                        if a.name == "time" or \
                                a.name.startswith("time."):
                            findings.append(Finding(
                                self.id, sf.path, node.lineno,
                                f"{sf.base} imports time (as "
                                f"{a.asname or a.name!r}) — the "
                                f"session transport must not even "
                                f"import the clock module; express "
                                f"deadlines as POLL_SLICE counts"))
                elif isinstance(node, ast.ImportFrom):
                    if node.module == "time":
                        findings.append(Finding(
                            self.id, sf.path, node.lineno,
                            f"{sf.base} imports from time — no "
                            f"clock symbols in the session "
                            f"transport"))
            clock_mods, clock_funcs = clock_aliases(sf.tree)
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                c = call_chain(node)
                if not c:
                    continue
                parts = c.split(".")
                bare_clock = (len(parts) == 1
                              and parts[0] in clock_funcs)
                mod_clock = (len(parts) == 2
                             and parts[0] in clock_mods
                             and parts[1] in CLOCK_CALLS)
                if bare_clock or mod_clock:
                    findings.append(Finding(
                        self.id, sf.path, node.lineno,
                        f"wall-clock read {c}() in {sf.base} — "
                        f"retry/backoff must be keyed to op seq / "
                        f"attempt index (slice-counted deadlines, "
                        f"select-based waits), never to a clock"))
        return findings


# =====================================================================
# pass 9: kernel-seam
# =====================================================================

# "Do Pallas kernels run here" has ONE definition, in
# framework/device.py, and callers ask it by attribute on that module
# (``device.use_pallas_kernels()``): a name bound at import is a copy a
# test's patch never reaches, and a second definition is a second
# answer. Behind it, a paged view's ``decode`` takes (q, k, v, t) and
# nothing that selects a path: the choice is ``_attend``'s.
SEAM_PREDICATE = "use_pallas_kernels"
SEAM_HOME = "device.py"


class KernelSeam:
    id = "kernel-seam"
    doc = ("the kernel-or-fallback predicate is defined once "
           "(framework/device.py) and reached by attribute, never "
           "bound by name or defined again; decode() of an is_paged "
           "view takes no use_kernel parameter")

    def run(self, files: List[SourceFile]) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files:
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.ImportFrom) and any(
                        a.name == SEAM_PREDICATE for a in node.names):
                    findings.append(Finding(
                        self.id, sf.path, node.lineno,
                        f"{SEAM_PREDICATE} bound by name at import — "
                        f"a patch on framework/device.py would not "
                        f"reach this module; import the module and "
                        f"call device.{SEAM_PREDICATE}()"))
                elif isinstance(node, ast.FunctionDef) and \
                        node.name == SEAM_PREDICATE and \
                        sf.base != SEAM_HOME:
                    findings.append(Finding(
                        self.id, sf.path, node.lineno,
                        f"a second definition of {SEAM_PREDICATE} — "
                        f"the predicate lives in framework/"
                        f"{SEAM_HOME} alone"))
            for cls in sf.classes():
                paged = any(
                    isinstance(st, ast.Assign)
                    and any(isinstance(t, ast.Name) and t.id == "is_paged"
                            for t in st.targets)
                    for st in cls.body)
                decode = methods_of(cls).get("decode")
                if not paged or decode is None:
                    continue
                a = decode.args
                for arg in a.args + a.kwonlyargs:
                    if arg.arg == "use_kernel":
                        findings.append(Finding(
                            self.id, sf.path, decode.lineno,
                            f"{cls.name}.decode takes use_kernel — a "
                            f"paged view's decode is (q, k, v, t); the "
                            f"path is chosen in one place "
                            f"(paged_cache._attend)"))
        return findings


# =====================================================================
# framework
# =====================================================================

PASSES = [SnapshotCompleteness(), HotPathPurity(), JournalCoverage(),
          ChargeDiscipline(), SpanSafety(), ExportDrift(),
          CompiledStepPurity(), NetClockPurity(), KernelSeam()]
PASS_IDS = [p.id for p in PASSES]

_SUPPRESS_RE = re.compile(r"#\s*lint:\s*ok\(([^)]*)\)")


def walk_files(root: str) -> Tuple[List[SourceFile], List[str]]:
    files: List[SourceFile] = []
    problems: List[str] = []
    if os.path.isfile(root):
        paths = [root]
    else:
        paths = []
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != "__pycache__")
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    paths.append(os.path.join(dirpath, fn))
    for p in paths:
        try:
            with open(p, encoding="utf-8") as f:
                src = f.read()
            tree = ast.parse(src, filename=p)
        except (OSError, SyntaxError, ValueError) as e:
            problems.append(f"{p}: unparseable: {e}")
            continue
        files.append(SourceFile(p, os.path.relpath(p),
                                tree, src.splitlines()))
    return files, problems


def _suppressed(f: Finding, files_by_path: Dict[str, SourceFile]) -> bool:
    sf = files_by_path.get(f.path)
    if sf is None:
        return False
    for ln in (f.line, f.line - 1):
        if 1 <= ln <= len(sf.lines):
            m = _SUPPRESS_RE.search(sf.lines[ln - 1])
            if m and f.pass_id in [s.strip()
                                   for s in m.group(1).split(",")]:
                return True
    return False


def run_passes(root: str, pass_ids: Optional[List[str]] = None):
    """(findings, suppressed, problems, n_files) — the library entry
    the self-tests drive."""
    files, problems = walk_files(root)
    if not files and problems:
        return [], [], problems, 0
    if not files:
        return [], [], [f"{root}: no python files found"], 0
    by_path = {sf.path: sf for sf in files}
    selected = [p for p in PASSES
                if pass_ids is None or p.id in pass_ids]
    findings: List[Finding] = []
    for p in selected:
        findings += p.run(files)
    findings.sort(key=Finding.key)
    kept = [f for f in findings if not _suppressed(f, by_path)]
    supp = [f for f in findings if _suppressed(f, by_path)]
    return kept, supp, problems, len(files)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="AST contract linter for the serving stack")
    ap.add_argument("root", nargs="?", default="paddle_tpu",
                    help="package directory (or single file) to lint")
    ap.add_argument("--pass", dest="passes", action="append",
                    choices=PASS_IDS, metavar="ID",
                    help="run only this pass (repeatable); "
                         f"ids: {', '.join(PASS_IDS)}")
    ap.add_argument("--list-passes", action="store_true",
                    help="print the pass table and exit")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable envelope "
                         "(paddle_tpu.report.v1, shared with the "
                         "other report doctors)")
    args = ap.parse_args(argv)

    if args.list_passes:
        for p in PASSES:
            print(f"{p.id:22s} {p.doc}")
        return 0

    if not os.path.exists(args.root):
        print(f"UNREADABLE: {args.root} does not exist")
        return 2

    kept, supp, problems, n_files = run_passes(args.root, args.passes)
    if problems and n_files == 0:
        for pr in problems:
            print(f"UNREADABLE: {pr}")
        return 2

    ok = not kept and not problems
    exit_code = 0 if ok else (2 if problems else 1)
    if args.json:
        emit_json(envelope(
            "check_static", ok, exit_code,
            {"root": args.root, "files_scanned": n_files,
             "passes": [p.id for p in PASSES
                        if args.passes is None or p.id in args.passes],
             "findings": [f.as_dict() for f in kept],
             "suppressed": [f.as_dict() for f in supp]},
            [repr(f) for f in kept] + problems))
        return exit_code

    for pr in problems:
        print(f"UNREADABLE: {pr}")
    for f in kept:
        print(repr(f))
    if supp:
        print(f"{len(supp)} finding(s) suppressed via "
              f"'# lint: ok(...)'")
    print(f"check_static: {len(kept)} finding(s) across {n_files} "
          f"file(s)" + (" — OK" if ok else ""))
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
