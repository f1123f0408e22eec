"""The xlint rules (1–10 here; the interprocedural concurrency rules
11–13 live in tools/xlint/concurrency.py, the exception-flow /
resource-lifecycle rules 14–16 in tools/xlint/lifecycle.py, and the
device-plane jit-boundary rules 17–19 in tools/xlint/tracewalk.py —
all registered into ``RULES`` below).
Each proves one invariant the serving/perf work depends on;
docs/STATIC_ANALYSIS.md records the incident that motivated each. All
analysis is stdlib ``ast`` — name/alias based, intentionally
under-approximate: a rule must never crash on odd code, and a miss is a
gap to close later, not a reason to over-report.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.xlint import Finding, Module, RepoTree

# ---------------------------------------------------------------------------
# Shared AST helpers
# ---------------------------------------------------------------------------


def _module_aliases(mod: Module) -> Dict[str, Set[str]]:
    """Names bound at module level to modules we care about:
    {"jax": {...}, "pltpu": {...}, "np": {...}, "functools": {...},
    "time": {...}}."""
    out: Dict[str, Set[str]] = {
        "jax": set(), "pltpu": set(), "np": set(), "functools": set(),
        "time": set()}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                bound = a.asname or a.name.split(".")[0]
                if a.name == "jax":
                    out["jax"].add(bound)
                elif a.name == "jax.experimental.pallas.tpu":
                    out["pltpu"].add(a.asname or a.name)
                elif a.name == "numpy":
                    out["np"].add(bound)
                elif a.name == "functools":
                    out["functools"].add(bound)
                elif a.name == "time":
                    out["time"].add(bound)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax.experimental.pallas":
                for a in node.names:
                    if a.name == "tpu":
                        out["pltpu"].add(a.asname or a.name)
    return out


def _is_call_to(node: ast.Call, aliases: Set[str], attr: str) -> bool:
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr == attr
            and isinstance(f.value, ast.Name) and f.value.id in aliases)


def _const_int_set(node: Optional[ast.AST]) -> Optional[Set[int]]:
    """Literal int / tuple-of-ints → set; None when non-literal."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[int] = set()
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                out.add(el.value)
            else:
                return None
        return out
    return None


def _qualname_of(stack: Sequence[ast.AST]) -> str:
    parts = [n.name for n in stack
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef))]
    return ".".join(parts) or "<module>"


class _ScopedVisitor(ast.NodeVisitor):
    """NodeVisitor that tracks the class/function nesting stack."""

    def __init__(self) -> None:
        self.stack: List[ast.AST] = []

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node)
        self.generic_visit(node)
        self.stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.stack.append(node)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef


# ---------------------------------------------------------------------------
# Rule 1: mosaic-compat
# ---------------------------------------------------------------------------

_COMPAT_MODULE = "xllm_service_tpu/ops/pallas/_compat.py"
# API names whose spelling differs across the jax/Mosaic versions this
# repo must run on (PR-1 regression: the pinned 0.4.x toolchain ships
# TPUCompilerParams/TPUMemorySpace; current jax ships
# CompilerParams/HBM). Only the one shim module may touch either
# spelling directly.
_PLTPU_FORBIDDEN = ("CompilerParams", "TPUCompilerParams", "HBM",
                    "TPUMemorySpace")
# jax.* surface that moved across the same versions (shard_map left
# experimental and grew check_vma; set_mesh is new-API-only).
_JAX_FORBIDDEN = ("shard_map", "set_mesh")
_FORBIDDEN_FROM_IMPORTS = {
    "jax.experimental.pallas.tpu": set(_PLTPU_FORBIDDEN),
    "jax.experimental.shard_map": {"shard_map"},
    "jax.experimental": {"shard_map"},
    "jax": set(_JAX_FORBIDDEN),
}


class MosaicCompatRule:
    """Contract: kernel code uses only the pallas/jax API names the
    pinned toolchain ships — names that moved or were renamed across
    versions (the mosaic breakage class) are called out at lint time
    instead of at first trace on hardware.

    Escape hatch: the per-rule allowlist for a deliberately
    version-gated call site (justify with the gating mechanism).

    Fixture: tests/xlint_fixtures/bad/.../ops/bad_mosaic.py."""

    name = "mosaic-compat"
    describe = ("version-sensitive pallas/jax API names "
                "(CompilerParams/HBM/shard_map/set_mesh) only via "
                "ops/pallas/_compat.py")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        for mod in tree.modules:
            if mod.path.endswith("ops/pallas/_compat.py"):
                continue
            aliases = _module_aliases(mod)
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Attribute) and \
                        isinstance(node.value, ast.Name):
                    base = node.value.id
                    sym = None
                    if base in aliases["pltpu"] and \
                            node.attr in _PLTPU_FORBIDDEN:
                        sym = f"pltpu.{node.attr}"
                    elif base in aliases["jax"] and \
                            node.attr in _JAX_FORBIDDEN:
                        sym = f"jax.{node.attr}"
                    if sym:
                        findings.append(Finding(
                            rule=self.name, path=mod.path,
                            line=node.lineno,
                            key=f"{mod.path}::{sym}",
                            message=f"direct {sym} — spell it via "
                                    f"{_COMPAT_MODULE} so both Mosaic "
                                    f"generations lower it"))
                elif isinstance(node, ast.ImportFrom):
                    banned = _FORBIDDEN_FROM_IMPORTS.get(
                        node.module or "")
                    if not banned:
                        continue
                    for a in node.names:
                        if a.name in banned:
                            sym = f"{node.module}.{a.name}"
                            findings.append(Finding(
                                rule=self.name, path=mod.path,
                                line=node.lineno,
                                key=f"{mod.path}::{sym}",
                                message=f"direct import of {sym} — "
                                        f"import the alias from "
                                        f"{_COMPAT_MODULE} instead"))
        return findings


# ---------------------------------------------------------------------------
# Rule 2: donation-coverage
# ---------------------------------------------------------------------------

# Parameter names that mean "this argument is a KV pool buffer" at the
# runtime/ jit boundaries. A jit whose signature carries one of these
# moves the pool across the host boundary every call: without donation
# XLA materializes a pool-sized copy per call, and without a layout pin
# (in_shardings/out_shardings, even best-effort via a **splat) layout
# assignment can re-introduce full-pool conversion copies — the exact
# regression tools/aot_copy_census.py caught in round 6.
_KV_PARAM_NAMES = {"kv", "kv_pages", "k_pages", "v_pages", "kv_cache"}
# Only the serving boundary is in scope: ops/ kernels also take
# k_pages/v_pages but run INSIDE the engine's jitted step, where
# donation is the outer jit's job (donating there would corrupt direct
# kernel-test callers' buffers).
_DONATION_SCOPE = ("runtime/",)


def _positional_params(fndef: ast.AST) -> List[str]:
    a = fndef.args
    return [p.arg for p in (*a.posonlyargs, *a.args)]


class DonationCoverageRule:
    """Contract: a runtime/ jax.jit entry point that takes a KV-pool
    array (param named kv/kv_pages/k_pages/v_pages/kv_cache) must
    donate it via donate_argnums — an undonated pool doubles peak HBM
    for the step. The device-plane generalisation (mesh-partitioned
    programs, partial/factory spellings, call-site dataflow) is rule
    18, ``sharded-donation`` in tools/xlint/tracewalk.py.

    Escape hatch: the allowlist, for pools genuinely read-only across
    the call (justify why no aliasing write exists).

    Fixture: tests/xlint_fixtures/bad/.../runtime/engine.py."""

    name = "donation-coverage"
    describe = ("runtime/ jax.jit entry points carrying KV-pool arrays "
                "must donate them and pin layouts")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        # Repo-wide function index for cross-module resolution (the
        # worker jits functions imported from models/).
        fn_index: Dict[str, List[ast.AST]] = {}
        for mod in tree.modules:
            for node in mod.tree.body:
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    fn_index.setdefault(node.name, []).append(node)
        for mod in tree.modules:
            if not any(s in mod.path for s in _DONATION_SCOPE):
                continue
            aliases = _module_aliases(mod)
            local = {n.name: n for n in mod.tree.body
                     if isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))}
            for site in self._jit_sites(mod, aliases):
                findings.extend(self._check_site(
                    mod, site, local, fn_index))
        return findings

    def _jit_sites(self, mod: Module, aliases) -> List[Tuple]:
        """→ [(wrapped_expr, jit_keywords, lineno)] for every jax.jit
        call — plain calls and functools.partial(jax.jit, ...)
        decorators."""
        sites = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and \
                    _is_call_to(node, aliases["jax"], "jit"):
                wrapped = node.args[0] if node.args else None
                sites.append((wrapped, node.keywords, node.lineno))
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call) and \
                            _is_call_to(dec, aliases["functools"],
                                        "partial") and dec.args and \
                            isinstance(dec.args[0], ast.Attribute) and \
                            dec.args[0].attr == "jit" and \
                            isinstance(dec.args[0].value, ast.Name) and \
                            dec.args[0].value.id in aliases["jax"]:
                        sites.append((node, dec.keywords, node.lineno))
                    elif isinstance(dec, ast.Attribute) and \
                            dec.attr == "jit" and \
                            isinstance(dec.value, ast.Name) and \
                            dec.value.id in aliases["jax"]:
                        # bare @jax.jit — no kwargs at all
                        sites.append((node, [], node.lineno))
        return sites

    def _check_site(self, mod: Module, site, local, fn_index
                    ) -> List[Finding]:
        wrapped, keywords, lineno = site
        fndef, n_bound = self._resolve(wrapped, local, fn_index, mod)
        if fndef is None:
            return []
        params = _positional_params(fndef)[n_bound:]
        kv_idx = [i for i, p in enumerate(params) if p in _KV_PARAM_NAMES]
        if not kv_idx:
            return []
        name = getattr(fndef, "name", "<lambda>")
        out: List[Finding] = []
        kw = {k.arg: k.value for k in keywords if k.arg is not None}
        has_splat = any(k.arg is None for k in keywords)
        donated = _const_int_set(kw.get("donate_argnums"))
        if "donate_argnums" in kw and donated is None:
            # Present but not a literal int/tuple: this is exactly the
            # site the rule exists for, so "can't verify" is a finding
            # (mirrors the non-literal make_lock check), not a pass.
            out.append(Finding(
                rule=self.name, path=mod.path, line=lineno,
                key=f"{mod.path}::{name}::donate-nonliteral",
                message=f"jax.jit of {name} carries KV-pool args but "
                        f"its donate_argnums is not a literal — the "
                        f"static checker cannot verify pool coverage; "
                        f"spell the indices inline"))
        elif any(i not in (donated or ()) for i in kv_idx):
            missing = [i for i in kv_idx if i not in (donated or ())]
            out.append(Finding(
                rule=self.name, path=mod.path, line=lineno,
                key=f"{mod.path}::{name}::donate",
                message=f"jax.jit of {name} carries KV-pool args at "
                        f"positions {kv_idx} but donate_argnums "
                        f"{'omits ' + str(missing) if donated is not None else 'is missing'}"
                        f" — every call will pay a pool-sized copy"))
        if not has_splat and "in_shardings" not in kw and \
                "out_shardings" not in kw:
            out.append(Finding(
                rule=self.name, path=mod.path, line=lineno,
                key=f"{mod.path}::{name}::layout-pin",
                message=f"jax.jit of {name} carries KV-pool args but "
                        f"pins no layouts (no in_/out_shardings and no "
                        f"**pin splat) — layout assignment can "
                        f"reintroduce full-pool conversion copies "
                        f"(tools/aot_copy_census.py, round 6)"))
        return out

    def _resolve(self, wrapped, local, fn_index, mod
                 ) -> Tuple[Optional[ast.AST], int]:
        """→ (function def or lambda, count of partial-bound positional
        args). None when the wrapped callable can't be resolved
        statically."""
        n_bound = 0
        if isinstance(wrapped, ast.Call):
            # functools.partial(fn, ...) — kwargs binding leaves
            # positional indexes unchanged; positional binding shifts.
            f = wrapped.func
            is_partial = (isinstance(f, ast.Attribute)
                          and f.attr == "partial") or \
                         (isinstance(f, ast.Name) and f.id == "partial")
            if is_partial and wrapped.args:
                n_bound = len(wrapped.args) - 1
                wrapped = wrapped.args[0]
            else:
                return None, 0
        if isinstance(wrapped, ast.Lambda):
            return wrapped, n_bound
        if isinstance(wrapped, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return wrapped, n_bound
        if isinstance(wrapped, ast.Name):
            if wrapped.id in local:
                return local[wrapped.id], n_bound
            cands = fn_index.get(wrapped.id, [])
            if len(cands) == 1:
                return cands[0], n_bound
        return None, 0


# ---------------------------------------------------------------------------
# Rule 3: lock-rank
# ---------------------------------------------------------------------------

# The canonical rank table. MUST stay in sync with the docstring table
# in xllm_service_tpu/utils/locks.py — the declaration check below makes
# an out-of-table make_lock a finding, so adding a lock means editing
# both (that is the point: the table is reviewed, not accreted).
LOCK_RANK_TABLE: Dict[str, int] = {
    "worker.hb": 5,
    "worker.reg": 8,
    "scheduler.req": 10,
    "worker.live": 10,
    "service.poison": 11,
    "worker.engine": 20,
    "kv_cache.tier": 22,
    "worker.kvfetch": 25,
    "worker.encstage": 26,
    "instance_mgr": 30,
    "kvcache_mgr": 35,
    "coordination_net": 60,
    "etcd.watches": 60,
    "store_guard": 74,
    "obs.failpoints": 75,
    "obs.slo": 78,
    "obs.watchdog": 79,
    "obs.events": 80,
    "obs.steptrace": 85,
    "obs.stepbooks": 86,
    "worker.embedcache": 87,
    "scheduler.elect": 88,
    "worker.addr": 89,
    "tracer": 90,
    "misc.pool": 90,
    "worker.vision": 90,
    "misc.counter": 91,
    "httpd.connpool": 92,
    "obs.registry": 93,
    "obs.spans": 94,
    "threads.book": 94,
    "hashing.native": 95,
    "native_httpd.lib": 96,
    "etcd_native.build": 97,
}


class LockRankRule:
    """Contract: every lock is created through make_lock with a rank
    from the canonical table (LOCK_RANK_TABLE here, mirrored in
    utils/locks.py), and lexically nested ``with`` acquisitions go
    strictly rank-upward. The interprocedural generalisation (cycles
    through call chains) is rule 11, ``lock-order-interprocedural``.

    Escape hatch: none for unranked locks; rank-order exceptions need
    a table change, not an allowlist entry.

    Fixture: tests/xlint_fixtures/bad/.../utils/bad_locks.py."""

    name = "lock-rank"
    describe = ("make_lock declarations match the rank table; nested "
                "lock scopes acquire in strictly increasing rank")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        decls = self._collect_decls(tree, findings)
        for mod in tree.modules:
            self._check_nesting(mod, decls, findings)
        return findings

    def _collect_decls(self, tree: RepoTree, findings: List[Finding]
                       ) -> Dict[Tuple[str, Optional[str], str],
                                 Tuple[str, int, bool]]:
        """(path, class, varname) → (lockname, rank, reentrant); also
        validates each declaration against the canonical table."""
        decls: Dict[Tuple[str, Optional[str], str],
                    Tuple[str, int, bool]] = {}
        for mod in tree.modules:
            rule = self

            class V(_ScopedVisitor):
                def visit_Assign(self, node: ast.Assign) -> None:
                    v = node.value
                    if isinstance(v, ast.Call) and \
                            isinstance(v.func, ast.Name) and \
                            v.func.id in ("make_lock", "make_rlock"):
                        rule._record_decl(mod, node, v,
                                          self.stack, decls, findings)
                    self.generic_visit(node)
            V().visit(mod.tree)
        return decls

    def _record_decl(self, mod: Module, assign: ast.Assign,
                     call: ast.Call, stack, decls,
                     findings: List[Finding]) -> None:
        args = call.args
        if len(args) < 2 or not all(
                isinstance(a, ast.Constant) for a in args[:2]):
            findings.append(Finding(
                rule=self.name, path=mod.path, line=call.lineno,
                key=f"{mod.path}::make_lock-nonliteral",
                message="make_lock/make_rlock with non-literal "
                        "name/rank — the static checker (and any "
                        "reader) can't verify it against the table"))
            return
        lockname, rank = args[0].value, args[1].value
        reentrant = call.func.id == "make_rlock"
        expect = LOCK_RANK_TABLE.get(lockname)
        if expect is None:
            findings.append(Finding(
                rule=self.name, path=mod.path, line=call.lineno,
                key=f"{mod.path}::{lockname}::undeclared",
                message=f"lock {lockname!r} (rank {rank}) is not in "
                        f"the rank table — add it to "
                        f"tools/xlint/rules.py LOCK_RANK_TABLE and the "
                        f"utils/locks.py docstring table"))
        elif expect != rank:
            findings.append(Finding(
                rule=self.name, path=mod.path, line=call.lineno,
                key=f"{mod.path}::{lockname}::rank-mismatch",
                message=f"lock {lockname!r} declared rank {rank} but "
                        f"the table says {expect}"))
        cls = next((n.name for n in reversed(stack)
                    if isinstance(n, ast.ClassDef)), None)
        for t in assign.targets:
            if isinstance(t, ast.Attribute):
                decls[(mod.path, cls, t.attr)] = (lockname, rank,
                                                  reentrant)
            elif isinstance(t, ast.Name):
                decls[(mod.path, None, t.id)] = (lockname, rank,
                                                 reentrant)

    @staticmethod
    def _lock_of(path: str, cls: Optional[str], expr: ast.AST, decls
                 ) -> Optional[Tuple[str, int, bool]]:
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id == "self":
            return decls.get((path, cls, expr.attr))
        if isinstance(expr, ast.Name):
            return decls.get((path, None, expr.id))
        return None

    def _check_nesting(self, mod: Module, decls,
                       findings: List[Finding]) -> None:
        # Call-mediated inversions (any depth) are rule 11's job
        # (tools/xlint/concurrency.py) — this rule keeps the
        # declaration check and the static nested-``with`` check only.
        rule = self

        class V(_ScopedVisitor):
            def __init__(self) -> None:
                super().__init__()
                self.held: List[Tuple[str, int, bool]] = []

            def _cls(self) -> Optional[str]:
                return next((n.name for n in reversed(self.stack)
                             if isinstance(n, ast.ClassDef)), None)

            def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
                # A new function body is a new acquisition scope: a
                # nested def's body runs later, not under the
                # lexically-enclosing with.
                old = self.held
                self.held = []
                super().visit_FunctionDef(node)
                self.held = old

            visit_AsyncFunctionDef = visit_FunctionDef

            def visit_With(self, node: ast.With) -> None:
                added = 0
                for item in node.items:
                    ent = rule._lock_of(mod.path, self._cls(),
                                        item.context_expr, decls)
                    if ent is None:
                        continue
                    lockname, rank, reentrant = ent
                    if self.held:
                        top_name, top_rank, top_re = self.held[-1]
                        # Re-entering a re-entrant lock the thread
                        # already holds is legal even with other locks
                        # acquired in between (the runtime checker
                        # short-circuits before the rank comparison).
                        same_reentrant = reentrant and any(
                            h[0] == lockname for h in self.held)
                        if top_rank >= rank and not same_reentrant:
                            findings.append(Finding(
                                rule=rule.name, path=mod.path,
                                line=node.lineno,
                                key=f"{mod.path}::"
                                    f"{_qualname_of(self.stack)}::"
                                    f"{top_name}<{lockname}",
                                message=f"acquires {lockname!r} (rank "
                                        f"{rank}) while holding "
                                        f"{top_name!r} (rank "
                                        f"{top_rank}) — lock order "
                                        f"must be strictly increasing "
                                        f"(utils/locks.py)"))
                    self.held.append(ent)
                    added += 1
                for s in node.body:
                    self.visit(s)
                for _ in range(added):
                    self.held.pop()

        V().visit(mod.tree)


# ---------------------------------------------------------------------------
# Rule 4: flag-registry
# ---------------------------------------------------------------------------

_FLAG_RE = re.compile(r"XLLM_[A-Z0-9_]+")
_FLAGS_DOC = "docs/FLAGS.md"


class FlagRegistryRule:
    """Contract: every XLLM_* environment read in the package appears
    in docs/FLAGS.md, and (on whole-package runs) every documented
    flag is still read somewhere — the flag surface cannot silently
    drift from its documentation in either direction.

    Second contract (flag discipline): flags are read at import or
    config time, never per-call on the serving path. A per-call
    ``os.environ.get`` inside a serving-reachable function costs a
    dict lookup + string parse per request, and — worse — makes the
    effective config mutable mid-flight: two requests in the same
    process can observe different values of the "same" knob. Reads
    inside ``__init__``/``from_env`` are config-time by definition
    and exempt (lazily-constructed singletons read once).

    Escape hatch: none for the registry direction — undocumented
    flags get documented, dead documentation gets deleted. Hot-path
    reads get hoisted to a config attribute; the allowlist exists
    for reads that are deliberately re-evaluated (none today).

    Fixture: tests/xlint_fixtures/bad/.../flags.py."""

    name = "flag-registry"
    describe = ("every XLLM_* env read appears in docs/FLAGS.md, every "
                "documented flag is actually read, and no flag is read "
                "per-call on the serving path")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        reads: Dict[str, Tuple[str, int]] = {}
        for mod in tree.modules:
            for name, line in self._env_reads(mod):
                reads.setdefault(name, (mod.path, line))
        doc = tree.read_text(_FLAGS_DOC)
        if doc is None:
            findings.append(Finding(
                rule=self.name, path=_FLAGS_DOC, line=0,
                key=f"{_FLAGS_DOC}::missing",
                message="docs/FLAGS.md not found — the flag registry "
                        "has nowhere to live"))
            return findings
        documented = set(_FLAG_RE.findall(doc))
        for name in sorted(set(reads) - documented):
            path, line = reads[name]
            findings.append(Finding(
                rule=self.name, path=path, line=line,
                key=f"flags::{name}",
                message=f"env gate {name} is read here but absent from "
                        f"docs/FLAGS.md — document it (semantics, "
                        f"default, interaction)"))
        # The reverse direction (documented-but-unread) is only sound
        # when the lint scope covers the whole package — a subtree run
        # (e.g. `--rule flag-registry xllm_service_tpu/service`) sees
        # only that subtree's reads and would call every other
        # documented flag stale.
        if tree.covers_package():
            for name in sorted(documented - set(reads)):
                findings.append(Finding(
                    rule=self.name, path=_FLAGS_DOC, line=0,
                    key=f"docs::{name}",
                    message=f"{name} is documented in docs/FLAGS.md "
                            f"but never read by package code — stale "
                            f"doc, or the read lives outside the "
                            f"package (allowlist with the real "
                            f"reader)"))
        findings.extend(self._hot_path_reads(tree))
        return findings

    def _hot_path_reads(self, tree: RepoTree) -> List[Finding]:
        """Flag discipline: an env read inside a serving-reachable
        function (per the rule-20 reachability graph) re-parses the
        environment per request. ``__init__`` and ``from_env`` are
        config-time scopes and exempt."""
        from tools.xlint.timeflow import timeflow_analyze
        tf = timeflow_analyze(tree)
        findings: List[Finding] = []
        # innermost enclosing function wins — nested defs have their
        # own FuncInfo and their own reachability verdict
        by_path: Dict[str, List] = {}
        for fi in tf.cg.functions.values():
            by_path.setdefault(fi.path, []).append(fi)
        for mod in tree.modules:
            for name, line in self._env_reads(mod):
                best = None
                for fi in by_path.get(mod.path, ()):
                    lo = fi.node.lineno
                    hi = getattr(fi.node, "end_lineno", lo) or lo
                    if lo <= line <= hi and (
                            best is None
                            or lo > best.node.lineno):
                        best = fi
                if best is None or best.fid not in tf.serving:
                    continue
                if best.name in ("__init__", "from_env"):
                    continue
                findings.append(Finding(
                    rule=self.name, path=mod.path, line=line,
                    key=f"{mod.path}::{best.qualname}::hotread:{name}",
                    message=f"env gate {name} is read per-call on the "
                            f"serving path — reachable via "
                            f"[{tf.witness(best.fid)}]; hoist the read "
                            f"to __init__/config time and thread the "
                            f"value through"))
        return findings

    @staticmethod
    def _env_reads(mod: Module) -> List[Tuple[str, int]]:
        out: List[Tuple[str, int]] = []

        def flag_const(node: ast.AST) -> Optional[str]:
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    _FLAG_RE.fullmatch(node.value):
                return node.value
            return None

        def is_environ(node: ast.AST) -> bool:
            return (isinstance(node, ast.Attribute)
                    and node.attr == "environ") or \
                   (isinstance(node, ast.Name) and node.id == "environ")

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                f = node.func
                is_read = False
                if isinstance(f, ast.Attribute):
                    if f.attr in ("get", "setdefault", "pop") and \
                            is_environ(f.value):
                        is_read = True
                    elif f.attr == "getenv":
                        is_read = True
                elif isinstance(f, ast.Name) and f.id == "getenv":
                    is_read = True
                if is_read and node.args:
                    name = flag_const(node.args[0])
                    if name:
                        out.append((name, node.lineno))
            elif isinstance(node, ast.Subscript) and \
                    is_environ(node.value):
                name = flag_const(node.slice)
                if name:
                    out.append((name, node.lineno))
        return out


# ---------------------------------------------------------------------------
# Rule 5: traced-host-sync
# ---------------------------------------------------------------------------

# Files whose functions can end up inside a jit trace. A host sync
# (.item(), np.asarray, device_get) inside a traced body either fails at
# trace time on abstract values or — worse, under some transforms —
# silently forces a device→host round trip per call.
_TRACED_SCOPE = ("xllm_service_tpu/models/", "xllm_service_tpu/ops/",
                 "xllm_service_tpu/runtime/engine.py")
_NP_SYNC_FNS = {"asarray", "array", "asanyarray", "ascontiguousarray",
                "copy"}
# Params that are static (trace-time Python) by convention across this
# codebase: configs/meshes, and the kernel wrappers' compile-time
# scalars (they flow into static_argnames jit params — the wrappers
# float()-normalize them so 0 vs 0.0 doesn't split the jit cache).
# Casts of these are trace-time Python, not host syncs.
_STATIC_PARAM_NAMES = {"cfg", "config", "mesh", "axis_name",
                       "scale", "logits_soft_cap"}


class TracedHostSyncRule:
    """Contract: code inside a jit-traced function (decorated, or
    named ``_traced_*``/``*_kernel``) never calls host-sync primitives
    — .item(), float()/int() on arrays, np.asarray, device_get. Under
    trace these either fail or silently insert a device→host sync per
    step.

    Escape hatch: the allowlist, for debug-only branches proven dead
    under trace (justify with the guard).

    Fixture: tests/xlint_fixtures/bad/.../models/bad_sync.py."""

    name = "traced-host-sync"
    describe = (".item()/np.asarray/device_get/host casts inside "
                "jit- or scan-traced bodies in models/, ops/, engine")

    def check(self, tree: RepoTree) -> List[Finding]:
        scoped = [m for m in tree.modules
                  if any(m.path.startswith(s) or m.path == s.rstrip("/")
                         for s in _TRACED_SCOPE)]
        index = self._function_index(scoped)
        roots = self._roots(scoped, index)
        reachable = self._closure(roots, index, scoped)
        findings: List[Finding] = []
        for mod, fndef in reachable:
            findings.extend(self._scan_traced(mod, fndef))
        return findings

    # -- call-graph construction ---------------------------------------
    @staticmethod
    def _function_index(scoped: List[Module]
                        ) -> Dict[str, List[Tuple[Module, ast.AST]]]:
        index: Dict[str, List[Tuple[Module, ast.AST]]] = {}
        for mod in scoped:
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    index.setdefault(node.name, []).append((mod, node))
        return index

    def _roots(self, scoped: List[Module], index
               ) -> List[Tuple[Module, ast.AST]]:
        roots: List[Tuple[Module, ast.AST]] = []
        for mod in scoped:
            aliases = _module_aliases(mod)
            local = {n.name: n for n in ast.walk(mod.tree)
                     if isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))}

            def resolve(expr) -> Optional[ast.AST]:
                if isinstance(expr, ast.Call):   # functools.partial(f,…)
                    f = expr.func
                    if ((isinstance(f, ast.Attribute)
                         and f.attr == "partial")
                        or (isinstance(f, ast.Name)
                            and f.id == "partial")) and expr.args:
                        return resolve(expr.args[0])
                    return None
                if isinstance(expr, ast.Name):
                    return local.get(expr.id)
                if isinstance(expr, ast.Lambda):
                    return expr
                return None

            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Call):
                    # jax.jit(f) / jax.jit(partial(f, …))
                    if _is_call_to(node, aliases["jax"], "jit") and \
                            node.args:
                        r = resolve(node.args[0])
                        if r is not None:
                            roots.append((mod, r))
                    # jax.lax.scan(body, …) / lax.scan(body, …): the
                    # body is traced wherever the scan call sits.
                    f = node.func
                    if isinstance(f, ast.Attribute) and \
                            f.attr == "scan" and node.args:
                        base = f.value
                        is_lax = (isinstance(base, ast.Name)
                                  and base.id == "lax") or \
                                 (isinstance(base, ast.Attribute)
                                  and base.attr == "lax")
                        if is_lax:
                            r = resolve(node.args[0])
                            if r is not None:
                                roots.append((mod, r))
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        is_jit = (isinstance(dec, ast.Attribute)
                                  and dec.attr == "jit") or \
                                 (isinstance(dec, ast.Call)
                                  and isinstance(dec.func,
                                                 ast.Attribute)
                                  and dec.func.attr in ("jit",)
                                  ) or \
                                 (isinstance(dec, ast.Call)
                                  and bool(dec.args)
                                  and isinstance(dec.args[0],
                                                 ast.Attribute)
                                  and dec.args[0].attr == "jit")
                        if is_jit:
                            roots.append((mod, node))
        return roots

    def _closure(self, roots, index, scoped
                 ) -> List[Tuple[Module, ast.AST]]:
        seen: Set[int] = set()
        out: List[Tuple[Module, ast.AST]] = []
        work = list(roots)
        while work:
            mod, fndef = work.pop()
            if id(fndef) in seen:
                continue
            seen.add(id(fndef))
            out.append((mod, fndef))
            # Edges: bare-name calls and module-attr calls whose
            # terminal name uniquely resolves within the scoped set.
            for node in ast.walk(fndef):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                callee = None
                if isinstance(f, ast.Name):
                    callee = f.id
                elif isinstance(f, ast.Attribute) and \
                        isinstance(f.value, ast.Name):
                    callee = f.attr
                if callee is None:
                    continue
                cands = index.get(callee, [])
                if len(cands) == 1:
                    work.append(cands[0])
        return out

    @staticmethod
    def _static_argnames(fndef: ast.AST) -> Set[str]:
        """Params a jit decorator declares static (static_argnames=):
        those are trace-time Python values, so host casts of them are
        legitimate."""
        out: Set[str] = set()
        for dec in getattr(fndef, "decorator_list", ()):
            if not isinstance(dec, ast.Call):
                continue
            for kw in dec.keywords:
                if kw.arg == "static_argnames":
                    v = kw.value
                    if isinstance(v, (ast.Tuple, ast.List)):
                        for el in v.elts:
                            if isinstance(el, ast.Constant) and \
                                    isinstance(el.value, str):
                                out.add(el.value)
                    elif isinstance(v, ast.Constant) and \
                            isinstance(v.value, str):
                        out.add(v.value)
        return out

    # -- the actual flags ----------------------------------------------
    def _scan_traced(self, mod: Module, fndef: ast.AST
                     ) -> List[Finding]:
        findings: List[Finding] = []
        aliases = _module_aliases(mod)
        name = getattr(fndef, "name", "<lambda>")
        a = fndef.args
        traced_params = {p.arg for p in (*a.posonlyargs, *a.args)
                         if p.arg not in _STATIC_PARAM_NAMES
                         and p.arg != "self"}
        traced_params -= self._static_argnames(fndef)

        def emit(node, what, why) -> None:
            findings.append(Finding(
                rule=self.name, path=mod.path, line=node.lineno,
                key=f"{mod.path}::{name}::{what}",
                message=f"{what} inside traced body {name}() — {why}"))

        for node in ast.walk(fndef):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                if f.attr in ("item", "tolist") and not node.args:
                    emit(node, f".{f.attr}()",
                         "forces a device→host sync per trace")
                elif f.attr in _NP_SYNC_FNS and \
                        isinstance(f.value, ast.Name) and \
                        f.value.id in aliases["np"]:
                    emit(node, f"np.{f.attr}",
                         "numpy materialization of a traced value")
                elif f.attr == "device_get" and \
                        isinstance(f.value, ast.Name) and \
                        f.value.id in aliases["jax"]:
                    emit(node, "jax.device_get",
                         "explicit device→host transfer")
            elif isinstance(f, ast.Name) and \
                    f.id in ("float", "int", "bool") and \
                    len(node.args) == 1 and \
                    isinstance(node.args[0], ast.Name) and \
                    node.args[0].id in traced_params:
                emit(node, f"{f.id}({node.args[0].id})",
                     "host cast of a (potentially traced) argument")
        return findings


# ---------------------------------------------------------------------------
# Rule 5b: hot-loop-blocking-readback
# ---------------------------------------------------------------------------

_ENGINE_FILE = "xllm_service_tpu/runtime/engine.py"
# The one sanctioned blocking-readback site: Engine._read_host starts an
# async device→host copy, waits with split device_wait/host_copy
# attribution, then materializes. Every other np.asarray/device_get on a
# device array inside an Engine method either hides a host sync in the
# serving loop (round 6's 5.9 s "readback" that was really
# unattributed device wait) or belongs on a justified allowlist entry
# for a genuinely cold path (PD KV export).
_READBACK_HELPER = "_read_host"


class HotLoopBlockingReadbackRule:
    """Contract: Engine methods on the decode hot loop
    (runtime/engine.py) perform blocking device→host readbacks
    (np.asarray / np.array / device_get / .item / float-casts) only
    inside the dedicated ``_read_host`` chokepoint, where the
    double-buffered overlap hides the sync — a stray readback
    serialises the pipeline.

    Escape hatch: route through ``_read_host``; the allowlist is for
    cold-path methods misclassified as hot (justify the call rate).

    Fixture: tests/xlint_fixtures/bad/.../runtime/engine.py."""

    name = "hot-loop-blocking-readback"
    describe = ("blocking device→host readbacks (np.asarray / np.array "
                "/ jax.device_get) inside Engine methods must go "
                "through Engine._read_host (async copy + "
                "device_wait/host_copy split attribution); cold paths "
                "need a justified allowlist entry")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        for mod in tree.modules:
            if mod.path != _ENGINE_FILE:
                continue
            aliases = _module_aliases(mod)
            for node in mod.tree.body:
                if not (isinstance(node, ast.ClassDef)
                        and node.name == "Engine"):
                    continue
                for item in node.body:
                    if not isinstance(item, (ast.FunctionDef,
                                             ast.AsyncFunctionDef)):
                        continue
                    if item.name == _READBACK_HELPER:
                        continue
                    findings.extend(self._scan(mod, item, aliases))
        return findings

    def _scan(self, mod: Module, fndef: ast.AST,
              aliases: Dict[str, Set[str]]) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(fndef):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)):
                continue
            if f.attr in ("asarray", "array") and \
                    f.value.id in aliases["np"]:
                what = f"np.{f.attr}"
            elif f.attr == "device_get" and f.value.id in aliases["jax"]:
                what = "jax.device_get"
            else:
                continue
            out.append(Finding(
                rule=self.name, path=mod.path, line=node.lineno,
                key=f"{mod.path}::Engine.{fndef.name}::{what}",
                message=f"{what} in Engine.{fndef.name}() blocks the "
                        f"host on a device readback — route it through "
                        f"Engine.{_READBACK_HELPER}() (async copy + "
                        f"device_wait/host_copy split attribution), or "
                        f"allowlist the cold path with a justification"))
        return out


# ---------------------------------------------------------------------------
# Rule 6: service-hygiene
# ---------------------------------------------------------------------------

# The httpd dispatch path: every function in these files runs on a
# request thread unless it is a dedicated background-thread target.
_SERVICE_FILES = (
    "xllm_service_tpu/service/httpd.py",
    "xllm_service_tpu/service/native_httpd.py",
    "xllm_service_tpu/service/http_service.py",
    "xllm_service_tpu/service/response_handler.py",
    "xllm_service_tpu/service/rpc_service.py",
)


class ServiceHygieneRule:
    """The broad-swallow check this rule used to carry moved to rule 16
    (``swallow-telemetry``, tools/xlint/lifecycle.py) — interprocedural
    and package-wide instead of lexical over five files."""

    name = "service-hygiene"
    describe = ("no blocking sleeps / unbounded .result() on the httpd "
                "dispatch path")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        for mod in tree.modules:
            if mod.path not in _SERVICE_FILES:
                continue
            thread_targets = self._thread_targets(mod)
            aliases = _module_aliases(mod)
            rule = self

            class V(_ScopedVisitor):
                def _in_thread_target(self) -> bool:
                    return any(
                        isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))
                        and n.name in thread_targets
                        for n in self.stack)

                def visit_Call(self, node: ast.Call) -> None:
                    f = node.func
                    if isinstance(f, ast.Attribute):
                        if f.attr == "sleep" and \
                                isinstance(f.value, ast.Name) and \
                                f.value.id in aliases["time"] and \
                                not self._in_thread_target():
                            findings.append(Finding(
                                rule=rule.name, path=mod.path,
                                line=node.lineno,
                                key=f"{mod.path}::"
                                    f"{_qualname_of(self.stack)}::"
                                    f"sleep",
                                message="time.sleep on the dispatch "
                                        "path blocks a request thread "
                                        "— use timeouts/events or a "
                                        "background thread"))
                        elif f.attr == "result" and not node.args and \
                                not node.keywords and \
                                not self._in_thread_target():
                            findings.append(Finding(
                                rule=rule.name, path=mod.path,
                                line=node.lineno,
                                key=f"{mod.path}::"
                                    f"{_qualname_of(self.stack)}::"
                                    f"result",
                                message=".result() with no timeout on "
                                        "the dispatch path — a wedged "
                                        "future pins the thread "
                                        "forever"))
                    self.generic_visit(node)
            V().visit(mod.tree)
        return findings

    @staticmethod
    def _thread_targets(mod: Module) -> Set[str]:
        targets: Set[str] = set()

        def record(v: ast.AST) -> None:
            if isinstance(v, ast.Attribute):
                targets.add(v.attr)
            elif isinstance(v, ast.Name):
                targets.add(v.id)

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg == "target":
                        record(kw.value)
                # utils/threads.spawn(name, target, ...) — positional
                f = node.func
                if ((isinstance(f, ast.Name) and f.id == "spawn")
                        or (isinstance(f, ast.Attribute)
                            and f.attr == "spawn")) \
                        and len(node.args) >= 2:
                    record(node.args[1])
        return targets


# ---------------------------------------------------------------------------
# Rule 7: metrics-registry
# ---------------------------------------------------------------------------

_OBS_DIR = "xllm_service_tpu/obs/"
# A hand-rolled Prometheus sample line inside an f-string: an xllm_-
# prefixed series name (this repo's namespace; interpolated fragments
# allowed — \x00 marks each FormattedValue in the template), an optional
# {label} section, whitespace, then an interpolated value. Name-only
# f-strings (registry keys like f"xllm_worker_{k}") carry no value
# interpolation after whitespace and do not match.
_EXPO_RE = re.compile(
    r"(?:^|[^A-Za-z0-9_:])"
    r"(xllm_[A-Za-z0-9_:\x00]*)"
    r"(?:\{[^{}]*\})?"
    r"[ \t]+\x00")


class MetricsRegistryRule:
    """Contract: Prometheus exposition is produced only by the
    obs/metrics.py registry — no hand-rolled ``# TYPE``/``# HELP``
    f-strings elsewhere — and every metric name referenced in tests or
    docs exists in the registry. Hand-rolled lines drift from the
    validated exposition format and break scrapers silently.

    Escape hatch: none — new metrics go through the registry.

    Fixture: tests/xlint_fixtures/bad/.../service/bad_metrics.py."""

    name = "metrics-registry"
    describe = ("no hand-rolled Prometheus exposition f-strings "
                "('name{...} value') outside xllm_service_tpu/obs/ — "
                "every /metrics line renders via the obs registry")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        rule = self
        for mod in tree.modules:
            if mod.path.startswith(_OBS_DIR):
                continue        # the one place exposition may be built

            class V(_ScopedVisitor):
                def visit_JoinedStr(self, node: ast.JoinedStr) -> None:
                    template = "".join(
                        part.value
                        if isinstance(part, ast.Constant)
                        and isinstance(part.value, str) else "\x00"
                        for part in node.values)
                    m = _EXPO_RE.search(template)
                    if m is not None:
                        series = m.group(1).replace("\x00", "*")
                        findings.append(Finding(
                            rule=rule.name, path=mod.path,
                            line=node.lineno,
                            key=f"{mod.path}::"
                                f"{_qualname_of(self.stack)}::{series}",
                            message=f"hand-rolled exposition line for "
                                    f"{series!r} — record it through "
                                    f"the obs registry (Counter/Gauge/"
                                    f"Histogram) and render /metrics "
                                    f"from Registry.render() instead"))
                    self.generic_visit(node)
            V().visit(mod.tree)
        return findings


# ---------------------------------------------------------------------------
# Rule 8: event-catalog
# ---------------------------------------------------------------------------

_EVENTS_MODULE = "xllm_service_tpu/obs/events.py"


def _load_string_tuple_catalog(tree: RepoTree, module_path: str,
                               symbol: str) -> Optional[Set[str]]:
    """A module-level all-string-literal tuple/list/set named ``symbol``
    from ``module_path`` — from the linted tree when in scope, else read
    from disk (subtree runs must judge against the same catalog the
    full run does). None when the module is missing or the literal
    can't be found."""
    mod = tree.get(module_path)
    if mod is not None:
        t = mod.tree
    else:
        src = tree.read_text(module_path)
        if src is None:
            return None
        try:
            t = ast.parse(src)
        except SyntaxError:
            return None
    for node in t.body:
        # Both plain and annotated module-level assignment shapes:
        # ``SECTIONS: Tuple[str, ...] = (...)`` declares a catalog just
        # as much as ``EVENT_TYPES = (...)`` does.
        if isinstance(node, ast.Assign):
            names = [x.id for x in node.targets
                     if isinstance(x, ast.Name)]
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name) and \
                node.value is not None:
            names = [node.target.id]
        else:
            continue
        if symbol in names:
            v = node.value
            if isinstance(v, (ast.Tuple, ast.List, ast.Set)):
                out: Set[str] = set()
                for el in v.elts:
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, str):
                        out.add(el.value)
                    else:
                        return None
                return out
    return None


def _load_event_catalog(tree: RepoTree) -> Optional[Set[str]]:
    """The ``EVENT_TYPES`` literal from obs/events.py."""
    return _load_string_tuple_catalog(tree, _EVENTS_MODULE,
                                      "EVENT_TYPES")


class EventCatalogRule:
    """Contract: every ``events.emit("<type>", ...)`` call site names
    a type from the obs/events.py catalog constant — free-string event
    types fragment the stream consumers key on.

    Escape hatch: none — new event types are added to the catalog
    first.

    Fixture: tests/xlint_fixtures/bad/.../service/bad_events.py."""

    name = "event-catalog"
    describe = ("every events.emit(\"<type>\", ...) call site uses a "
                "type declared in the obs/events.py EVENT_TYPES catalog "
                "(closed taxonomy)")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        catalog = _load_event_catalog(tree)
        for mod in tree.modules:
            if mod.path == _EVENTS_MODULE:
                continue        # the catalog module itself
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "emit"
                        and self._is_events_receiver(node.func.value)):
                    continue
                if catalog is None:
                    findings.append(Finding(
                        rule=self.name, path=mod.path, line=node.lineno,
                        key=f"{mod.path}::catalog-missing",
                        message=f"events.emit() call but no EVENT_TYPES "
                                f"literal found in {_EVENTS_MODULE} — "
                                f"the closed taxonomy has nowhere to "
                                f"live"))
                    continue
                arg = node.args[0] if node.args else None
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str):
                    if arg.value not in catalog:
                        findings.append(Finding(
                            rule=self.name, path=mod.path,
                            line=node.lineno,
                            key=f"{mod.path}::event::{arg.value}",
                            message=f"event type {arg.value!r} is not "
                                    f"declared in the {_EVENTS_MODULE} "
                                    f"EVENT_TYPES catalog — add it "
                                    f"there (and to the "
                                    f"docs/OBSERVABILITY.md taxonomy) "
                                    f"or fix the spelling"))
                else:
                    findings.append(Finding(
                        rule=self.name, path=mod.path, line=node.lineno,
                        key=f"{mod.path}::event-nonliteral",
                        message="events.emit() with a non-literal type "
                                "— the static checker cannot verify it "
                                "against the catalog; spell the type "
                                "inline"))
        return findings

    @staticmethod
    def _is_events_receiver(expr: ast.AST) -> bool:
        """The receiver looks like an event log: its terminal name is
        ``events`` / ``_events`` / ``*_events`` (``self.events``,
        ``self.http_service.events``, a bare ``events`` local). Name-
        based on purpose: unrelated ``.emit()`` APIs (loggers, signal
        buses) keep their own namespaces."""
        name = None
        if isinstance(expr, ast.Name):
            name = expr.id
        elif isinstance(expr, ast.Attribute):
            name = expr.attr
        return name is not None and (name == "events"
                                     or name.endswith("_events"))


# ---------------------------------------------------------------------------
# Rule 10: failpoint-catalog
# ---------------------------------------------------------------------------

_FAILPOINTS_MODULE = "xllm_service_tpu/obs/failpoints.py"


def _load_failpoint_catalog(tree: RepoTree) -> Optional[Set[str]]:
    """The ``FAILPOINTS`` literal from obs/failpoints.py."""
    return _load_string_tuple_catalog(tree, _FAILPOINTS_MODULE,
                                      "FAILPOINTS")


class FailpointCatalogRule:
    """Contract: every ``failpoints.fire("<name>")`` site names a
    registered failpoint, and (whole-package runs) every registered
    failpoint is armed by at least one test — an unfired failpoint is
    untested recovery code.

    Escape hatch: none — register the failpoint and arm it in a test.

    Fixture: tests/xlint_fixtures/bad/.../service/bad_failpoints.py."""

    name = "failpoint-catalog"
    describe = ("every failpoints.fire(\"<name>\") call site uses a "
                "name declared in the obs/failpoints.py FAILPOINTS "
                "catalog (closed taxonomy, like event-catalog)")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        catalog = _load_failpoint_catalog(tree)
        for mod in tree.modules:
            if mod.path == _FAILPOINTS_MODULE:
                continue        # the catalog module itself
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "fire"
                        and self._is_failpoints_receiver(
                            node.func.value)):
                    continue
                if catalog is None:
                    findings.append(Finding(
                        rule=self.name, path=mod.path, line=node.lineno,
                        key=f"{mod.path}::catalog-missing",
                        message=f"failpoints.fire() call but no "
                                f"FAILPOINTS literal found in "
                                f"{_FAILPOINTS_MODULE} — the closed "
                                f"catalog has nowhere to live"))
                    continue
                arg = node.args[0] if node.args else None
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str):
                    if arg.value not in catalog:
                        findings.append(Finding(
                            rule=self.name, path=mod.path,
                            line=node.lineno,
                            key=f"{mod.path}::failpoint::{arg.value}",
                            message=f"failpoint {arg.value!r} is not "
                                    f"declared in the "
                                    f"{_FAILPOINTS_MODULE} FAILPOINTS "
                                    f"catalog — add it there (and to "
                                    f"docs/ROBUSTNESS.md) or fix the "
                                    f"spelling"))
                else:
                    findings.append(Finding(
                        rule=self.name, path=mod.path, line=node.lineno,
                        key=f"{mod.path}::failpoint-nonliteral",
                        message="failpoints.fire() with a non-literal "
                                "name — the static checker cannot "
                                "verify it against the catalog; spell "
                                "the name inline"))
        return findings

    @staticmethod
    def _is_failpoints_receiver(expr: ast.AST) -> bool:
        """The receiver looks like a failpoint set: terminal name
        ``failpoints`` / ``_failpoints`` / ``*_failpoints`` (mirrors
        EventCatalogRule's name-based namespace)."""
        name = None
        if isinstance(expr, ast.Name):
            name = expr.id
        elif isinstance(expr, ast.Attribute):
            name = expr.attr
        return name is not None and (name == "failpoints"
                                     or name.endswith("_failpoints"))


# ---------------------------------------------------------------------------
# Rule 23: hotpath-section-catalog
# ---------------------------------------------------------------------------

_PROFILER_MODULE = "xllm_service_tpu/obs/profiler.py"


def _load_section_catalog(tree: RepoTree) -> Optional[Set[str]]:
    """The ``SECTIONS`` literal from obs/profiler.py."""
    return _load_string_tuple_catalog(tree, _PROFILER_MODULE,
                                      "SECTIONS")


class HotpathSectionCatalogRule:
    """Contract: every ``profiler.section("<name>")`` call site names a
    section from the obs/profiler.py ``SECTIONS`` catalog — the hot-path
    timing taxonomy is CLOSED. A free-string section would mint a new
    ``xllm_service_hotpath_ms{section=...}`` series no dashboard or
    saturation sweep knows to read, and (worse) would only fail at
    runtime on the serving path, since ``section()`` raises on unknown
    names.

    Escape hatch: none — new sections are added to the catalog first
    (and to the docs/OBSERVABILITY.md table).

    Fixture: tests/xlint_fixtures/bad/.../service/bad_sections.py."""

    name = "hotpath-section-catalog"
    describe = ("every profiler.section(\"<name>\") call site uses a "
                "section declared in the obs/profiler.py SECTIONS "
                "catalog (closed hot-path timing taxonomy)")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        catalog = _load_section_catalog(tree)
        for mod in tree.modules:
            if mod.path == _PROFILER_MODULE:
                continue        # the catalog module itself
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "section"
                        and self._is_profiler_receiver(node.func.value)):
                    continue
                if catalog is None:
                    findings.append(Finding(
                        rule=self.name, path=mod.path, line=node.lineno,
                        key=f"{mod.path}::catalog-missing",
                        message=f"profiler.section() call but no "
                                f"SECTIONS literal found in "
                                f"{_PROFILER_MODULE} — the closed "
                                f"timing taxonomy has nowhere to live"))
                    continue
                arg = node.args[0] if node.args else None
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str):
                    if arg.value not in catalog:
                        findings.append(Finding(
                            rule=self.name, path=mod.path,
                            line=node.lineno,
                            key=f"{mod.path}::section::{arg.value}",
                            message=f"hot-path section {arg.value!r} "
                                    f"is not declared in the "
                                    f"{_PROFILER_MODULE} SECTIONS "
                                    f"catalog — add it there (and to "
                                    f"docs/OBSERVABILITY.md) or fix "
                                    f"the spelling; section() raises "
                                    f"on unknown names AT RUNTIME, on "
                                    f"the serving path"))
                else:
                    findings.append(Finding(
                        rule=self.name, path=mod.path, line=node.lineno,
                        key=f"{mod.path}::section-nonliteral",
                        message="profiler.section() with a non-literal "
                                "name — the static checker cannot "
                                "verify it against the catalog; spell "
                                "the section inline"))
        return findings

    @staticmethod
    def _is_profiler_receiver(expr: ast.AST) -> bool:
        """The receiver looks like the hot-path profiler: terminal name
        ``profiler`` / ``_profiler`` / ``*_profiler`` (mirrors
        EventCatalogRule's name-based namespace — unrelated
        ``.section()`` APIs like configparser keep theirs)."""
        name = None
        if isinstance(expr, ast.Name):
            name = expr.id
        elif isinstance(expr, ast.Attribute):
            name = expr.attr
        return name is not None and (name == "profiler"
                                     or name.endswith("_profiler"))


# ---------------------------------------------------------------------------
# Rule 24: steptrace-schema
# ---------------------------------------------------------------------------

_STEPTRACE_MODULE = "xllm_service_tpu/obs/steptrace.py"
_TIMELINE_MODULE = "xllm_service_tpu/obs/timeline.py"


def _load_step_field_catalog(tree: RepoTree) -> Optional[Set[str]]:
    """The ``STEP_FIELDS`` literal from obs/steptrace.py."""
    return _load_string_tuple_catalog(tree, _STEPTRACE_MODULE,
                                      "STEP_FIELDS")


def _load_chrome_phase_catalog(tree: RepoTree) -> Optional[Set[str]]:
    """The ``CHROME_PHASES`` literal from obs/timeline.py."""
    return _load_string_tuple_catalog(tree, _TIMELINE_MODULE,
                                      "CHROME_PHASES")


class SteptraceSchemaRule:
    """Contract: the step flight-recorder schema and the chrome-trace
    phase vocabulary are CLOSED. Every ``steptrace.record(<field>=...)``
    keyword names a field from the obs/steptrace.py ``STEP_FIELDS``
    catalog (a free-keyed record would raise at runtime, on the engine
    loop), and every ``{"ph": "<phase>"}`` dict literal uses a phase
    from the obs/timeline.py ``CHROME_PHASES`` catalog — chrome://
    tracing silently DROPS events with unknown phases, so a typo'd
    emitter renders as a mysteriously empty track, not an error.

    Escape hatch: none — new fields/phases are added to the catalogs
    first (and to the docs/OBSERVABILITY.md schema table).

    Fixture: tests/xlint_fixtures/bad/.../service/bad_steptrace.py."""

    name = "steptrace-schema"
    describe = ("steptrace.record(field=...) keywords are pinned to the "
                "obs/steptrace.py STEP_FIELDS catalog and {\"ph\": ...} "
                "chrome-trace literals to the obs/timeline.py "
                "CHROME_PHASES catalog (both closed)")

    def check(self, tree: RepoTree) -> List[Finding]:
        findings: List[Finding] = []
        fields = _load_step_field_catalog(tree)
        phases = _load_chrome_phase_catalog(tree)
        for mod in tree.modules:
            if mod.path in (_STEPTRACE_MODULE, _TIMELINE_MODULE):
                continue        # the catalog modules themselves
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "record" and \
                        self._is_steptrace_receiver(node.func.value):
                    findings.extend(self._check_record(
                        mod.path, node, fields))
                elif isinstance(node, ast.Dict):
                    findings.extend(self._check_ph_dict(
                        mod.path, node, phases))
        return findings

    def _check_record(self, path: str, node: ast.Call,
                      fields: Optional[Set[str]]) -> List[Finding]:
        out: List[Finding] = []
        if fields is None:
            return [Finding(
                rule=self.name, path=path, line=node.lineno,
                key=f"{path}::fields-missing",
                message=f"steptrace.record() call but no STEP_FIELDS "
                        f"literal found in {_STEPTRACE_MODULE} — the "
                        f"closed step-record schema has nowhere to "
                        f"live")]
        for kw in node.keywords:
            if kw.arg is None:
                out.append(Finding(
                    rule=self.name, path=path, line=node.lineno,
                    key=f"{path}::record-splat",
                    message="steptrace.record(**kwargs) with a splat — "
                            "the static checker cannot verify the "
                            "field names; spell them inline"))
            elif kw.arg not in fields:
                out.append(Finding(
                    rule=self.name, path=path, line=node.lineno,
                    key=f"{path}::field::{kw.arg}",
                    message=f"step-record field {kw.arg!r} is not "
                            f"declared in the {_STEPTRACE_MODULE} "
                            f"STEP_FIELDS catalog — add it there (and "
                            f"to docs/OBSERVABILITY.md) or fix the "
                            f"spelling; record() raises on unknown "
                            f"fields AT RUNTIME, on the engine loop"))
        return out

    def _check_ph_dict(self, path: str, node: ast.Dict,
                       phases: Optional[Set[str]]) -> List[Finding]:
        for k, v in zip(node.keys, node.values):
            if not (isinstance(k, ast.Constant) and k.value == "ph"):
                continue
            if phases is None:
                return [Finding(
                    rule=self.name, path=path, line=node.lineno,
                    key=f"{path}::phases-missing",
                    message=f"chrome-trace event literal but no "
                            f"CHROME_PHASES catalog found in "
                            f"{_TIMELINE_MODULE}")]
            if isinstance(v, ast.Constant) and \
                    isinstance(v.value, str):
                if v.value not in phases:
                    return [Finding(
                        rule=self.name, path=path, line=node.lineno,
                        key=f"{path}::ph::{v.value}",
                        message=f"chrome-trace phase {v.value!r} is "
                                f"not in the {_TIMELINE_MODULE} "
                                f"CHROME_PHASES catalog — tracing UIs "
                                f"silently drop unknown phases; add "
                                f"it there or fix the spelling")]
            else:
                return [Finding(
                    rule=self.name, path=path, line=node.lineno,
                    key=f"{path}::ph-nonliteral",
                    message="chrome-trace event with a non-literal "
                            "\"ph\" — the static checker cannot "
                            "verify it against CHROME_PHASES; spell "
                            "the phase inline")]
        return []

    @staticmethod
    def _is_steptrace_receiver(expr: ast.AST) -> bool:
        """The receiver looks like the step flight recorder: terminal
        name ``steptrace`` / ``_steptrace`` / ``*_steptrace`` (the same
        name-based namespace convention as the event/failpoint/section
        catalog rules — unrelated ``.record()`` APIs keep theirs)."""
        name = None
        if isinstance(expr, ast.Name):
            name = expr.id
        elif isinstance(expr, ast.Attribute):
            name = expr.attr
        return name is not None and (name == "steptrace"
                                     or name.endswith("_steptrace"))


from tools.xlint.concurrency import (         # noqa: E402 — rules 11–13
    BlockingUnderLockRule, LockOrderInterproceduralRule,
    ThreadRootRaceRule)
from tools.xlint.lifecycle import (           # noqa: E402 — rules 14–16
    ResourceLeakRule, SwallowTelemetryRule, ThreadRootCrashRule)
from tools.xlint.tracewalk import (           # noqa: E402 — rules 17–19
    RecompileHazardRule, ShardedDonationRule, TransferDisciplineRule)
from tools.xlint.timeflow import (            # noqa: E402 — rules 20–22
    DeadlinePropagationRule, RetryDisciplineRule, UnboundedIoRule)

RULES = [
    MosaicCompatRule(),
    DonationCoverageRule(),
    LockRankRule(),
    FlagRegistryRule(),
    TracedHostSyncRule(),
    HotLoopBlockingReadbackRule(),
    ServiceHygieneRule(),
    MetricsRegistryRule(),
    EventCatalogRule(),
    FailpointCatalogRule(),
    LockOrderInterproceduralRule(),
    BlockingUnderLockRule(),
    ThreadRootRaceRule(LOCK_RANK_TABLE),
    ThreadRootCrashRule(),
    ResourceLeakRule(),
    SwallowTelemetryRule(),
    RecompileHazardRule(),
    ShardedDonationRule(),
    TransferDisciplineRule(),
    UnboundedIoRule(),
    DeadlinePropagationRule(),
    RetryDisciplineRule(),
    HotpathSectionCatalogRule(),
    SteptraceSchemaRule(),
]
