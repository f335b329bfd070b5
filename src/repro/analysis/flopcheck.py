"""flopcheck — repo-specific AST linter for the JAX/Pallas hot paths.

Every rule encodes an invariant a past PR broke by hand (docs/analysis.md
carries the catalog with the historical bug each rule would have caught):

  FC-HOSTSYNC    hidden per-step host syncs: ``float()/int()/bool()/
                 .item()/np.asarray`` on values that dataflow from jitted
                 step outputs inside per-step loops, or eager conversion
                 of device computations in the Trainer/OnlineEngine tick
                 paths (the PR-4 ``float(sched(i))`` LR bug).  Values
                 drained through ``jax.device_get`` are host data and
                 never flag.
  FC-RECOMPILE   recompile hazards: ``jax.jit``/``shard_map`` constructed
                 inside a loop (a fresh jit wrapper per iteration defeats
                 the compile cache), and unhashable freshly-constructed
                 objects (lambdas, dict/list/set literals, non-frozen
                 dataclasses) passed in ``static_argnums``/
                 ``static_argnames`` positions.
  FC-PALLAS      Pallas tracing pitfalls: ``pl.program_id`` inside a
                 ``pl.when`` region (the PR-1 interpret-mode bug — the
                 evaluator does not substitute program ids inside the
                 sub-jaxpr), side-effecting host calls (``print``,
                 ``time.time`` ...) inside kernel bodies, and
                 ``pl.pallas_call`` sites that do not plumb ``interpret=``.
  FC-DONATE      reuse of a buffer after it was passed at a
                 ``donate_argnums`` position of a jitted call in the same
                 scope — the buffer is deleted at dispatch.
  FC-LOCK        methods of classes owning a ``threading.Lock/RLock``
                 that WRITE lock-guarded attributes without holding it
                 (the DataPipeline main-thread/prefetcher race fixed by
                 hand in PR 4).  Private (``_``-prefixed) methods are
                 assumed to be called under the lock and are not flagged.
  FC-DEPRECATED  removed/renamed jax APIs (``jax.tree_map`` et al.).
  FC-TELEMETRY   host clock reads (``time.time``/``perf_counter``/
                 ``monotonic``) or telemetry-registry writes
                 (``.observe``/``.inc``/``.sample`` on metric objects)
                 inside a jit-traced body — both run ONCE at trace time,
                 so the compiled step bakes in a stale constant and the
                 metric never updates again.  Time and record around the
                 jitted call on the host (the OnlineEngine/Trainer
                 idiom), never inside it.

Suppression: append ``# flopcheck: disable=FC-RULE`` (comma-separate for
several rules) to the flagged line, or put it on its own line directly
above; ``# flopcheck: disable-file=FC-RULE`` anywhere disables a rule for
the whole file.  ``scripts/flopcheck.py --strict`` requires every
violation to be suppressed *with a comment* — silent violations fail CI.

The analysis is intraprocedural and heuristic by design: it trades
soundness for zero-configuration signal on this repo's idioms (jitted
handles are recognized by ``jax.jit``/``shard_map`` assignments and by
the ``make_*``/``jit_*``/``for_accum`` factory naming convention).
"""
from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "FC-HOSTSYNC": "hidden per-step host sync on device values",
    "FC-RECOMPILE": "jit/shard_map recompile hazard",
    "FC-PALLAS": "Pallas kernel tracing pitfall",
    "FC-DONATE": "donated buffer reused after the donating call",
    "FC-LOCK": "lock-guarded attribute written without the lock",
    "FC-DEPRECATED": "removed/renamed jax API",
    "FC-TELEMETRY": "host timing/metrics call inside a jit-traced body",
}

# host clock callees flagged inside traced bodies (module attr or bare
# name imported via `from time import ...`)
HOST_CLOCK_CALLS = {
    "time", "perf_counter", "perf_counter_ns", "monotonic",
    "monotonic_ns", "process_time", "time_ns",
}
# metric-object write methods (MetricsRegistry children + XPUTimer ring)
METRIC_WRITE_ATTRS = {"observe", "inc", "sample"}
# receivers whose `.sample`/`.inc` are NOT metrics (random.sample,
# np.random.sample, jnp-keyed samplers)
METRIC_SAFE_ROOTS = {"np", "numpy", "random", "jax", "jnp", "secrets"}

# jax APIs removed around 0.4.x -> replacement hint
DEPRECATED_APIS: Dict[str, str] = {
    "jax.tree_map": "jax.tree.map (or jax.tree_util.tree_map)",
    "jax.tree_multimap": "jax.tree.map",
    "jax.tree_flatten": "jax.tree.flatten",
    "jax.tree_unflatten": "jax.tree.unflatten",
    "jax.tree_leaves": "jax.tree.leaves",
    "jax.tree_structure": "jax.tree.structure",
    "jax.tree_transpose": "jax.tree_util.tree_transpose",
    "jax.tree_all": "jax.tree.all",
    "jax.xla_computation": "jax.jit(fn).lower(...)",
    "jax.abstract_arrays": "jax.core",
}

# factories whose return value is a jitted/shard_mapped step function
HANDLE_MAKER_RE = re.compile(r"^(make_|jit_)\w+$|^for_accum$")
# repo-known donation signatures: Runner.jit_train_step /
# StagedTrainStep.for_accum donate at least (params, opt_state) unless
# built with a literal donate=False
KNOWN_DONATING_MAKERS = {"jit_train_step": (0, 1), "for_accum": (0, 1)}

# hot per-step loops: the Trainer train loop and the OnlineEngine tick
# paths (plus anything matching the naming convention)
HOT_CLASSES = {"Trainer", "OnlineEngine"}
HOT_FUNC_RE = re.compile(r"^(train|tick|_drain)$|_tick$")

# callees whose results are host data (safe to convert per-step)
HOST_SAFE_LAST = {
    "host", "device_get", "len", "min", "max", "abs", "sum", "round",
    "perf_counter", "time", "monotonic", "get", "item_host", "range",
    "lr_scale_for", "stage_for", "accum_for", "batch_for", "int", "float",
    "bool", "str", "enumerate", "zip", "sorted", "count",
}
HOST_SAFE_ROOTS = {"np", "numpy", "math", "time", "os", "random"}

CONVERTERS = {"float", "int", "bool"}
MUTATORS = {"append", "appendleft", "extend", "add", "remove", "discard",
            "pop", "popleft", "clear", "update", "insert", "setdefault"}

SUPPRESS_RE = re.compile(
    r"#\s*flopcheck:\s*(disable|disable-file)\s*=\s*([A-Z0-9,\-\s]+)")


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    path: str
    line: int
    col: int
    message: str
    suppressed: bool = False

    def format(self) -> str:
        tag = " (suppressed)" if self.suppressed else ""
        return f"{self.path}:{self.line}:{self.col}: {self.rule}{tag} " \
               f"{self.message}"


@dataclasses.dataclass
class HandleInfo:
    """What we know about a jitted-callable binding."""
    donate: Tuple[int, ...] = ()
    static_nums: Tuple[int, ...] = ()
    static_names: Tuple[str, ...] = ()


@dataclasses.dataclass
class Registry:
    """Cross-file facts collected in a first pass over every checked file:
    functions jitted with static args (decorator form) and dataclasses
    whose instances are unhashable (would retrace every call as a static
    arg)."""
    static_fns: Dict[str, Tuple[Tuple[str, ...], Tuple[str, ...]]] = \
        dataclasses.field(default_factory=dict)   # name -> (params, static)
    unhashable_dataclasses: Set[str] = dataclasses.field(default_factory=set)


# ---------------------------------------------------------------------------
# small AST helpers
# ---------------------------------------------------------------------------


def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last(name: str) -> str:
    return name.rsplit(".", 1)[-1]


def _root(name: str) -> str:
    return name.split(".", 1)[0]


def _tuple_ints(node: ast.AST) -> Tuple[int, ...]:
    """Literal int tuple (handles the `(1,) if donate else ()` idiom by
    taking the non-empty branch)."""
    if isinstance(node, ast.IfExp):
        return _tuple_ints(node.body) or _tuple_ints(node.orelse)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return tuple(out)
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    return ()


def _tuple_strs(node: ast.AST) -> Tuple[str, ...]:
    if isinstance(node, ast.IfExp):
        return _tuple_strs(node.body) or _tuple_strs(node.orelse)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str))
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    return ()


def _is_unhashable_literal(node: ast.AST) -> Optional[str]:
    if isinstance(node, (ast.Dict, ast.DictComp)):
        return "dict"
    if isinstance(node, (ast.List, ast.ListComp)):
        return "list"
    if isinstance(node, (ast.Set, ast.SetComp)):
        return "set"
    if isinstance(node, ast.Lambda):
        return "lambda"
    return None


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _has_double_star(call: ast.Call) -> bool:
    return any(k.arg is None for k in call.keywords)


def _names_in(node: ast.AST) -> Set[str]:
    """All dotted names read anywhere inside an expression."""
    out: Set[str] = set()
    for n in ast.walk(node):
        d = dotted(n)
        if d:
            out.add(d)
    return out


def _assign_targets(stmt: ast.AST) -> List[ast.AST]:
    if isinstance(stmt, ast.Assign):
        out: List[ast.AST] = []
        for t in stmt.targets:
            out.extend(t.elts if isinstance(t, (ast.Tuple, ast.List))
                       else [t])
        return out
    if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        return [stmt.target]
    return []


def _target_names(stmt: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for t in _assign_targets(stmt):
        d = dotted(t)
        if d:
            out.add(d)
    return out


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------


def _suppressions(source: str) -> Tuple[Dict[int, Set[str]], Set[str]]:
    """(line -> rules disabled on that line, rules disabled file-wide).
    A standalone suppression comment also covers the next line."""
    per_line: Dict[int, Set[str]] = {}
    file_wide: Set[str] = set()
    try:
        toks = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenError, IndentationError):
        return per_line, file_wide
    code_lines = {t.start[0] for t in toks
                  if t.type not in (tokenize.COMMENT, tokenize.NL,
                                    tokenize.NEWLINE, tokenize.INDENT,
                                    tokenize.DEDENT, tokenize.ENDMARKER)}
    for t in toks:
        if t.type != tokenize.COMMENT:
            continue
        m = SUPPRESS_RE.search(t.string)
        if not m:
            continue
        rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
        if m.group(1) == "disable-file":
            file_wide |= rules
            continue
        line = t.start[0]
        per_line.setdefault(line, set()).update(rules)
        if line not in code_lines:          # standalone comment line
            per_line.setdefault(line + 1, set()).update(rules)
    return per_line, file_wide


# ---------------------------------------------------------------------------
# registry pass (cross-file)
# ---------------------------------------------------------------------------


def _registry_scan(tree: ast.AST, reg: Registry):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                d = dotted(dec.func)
                inner = dec.args[0] if dec.args else None
                if (d and _last(d) == "partial" and inner is not None
                        and (dotted(inner) or "").endswith("jit")):
                    statics = _tuple_strs(_kw(dec, "static_argnames")
                                          or ast.Constant(value=None))
                    nums = _tuple_ints(_kw(dec, "static_argnums")
                                       or ast.Constant(value=None))
                    params = tuple(a.arg for a in node.args.args)
                    names = set(statics) | {params[i] for i in nums
                                            if i < len(params)}
                    if names:
                        reg.static_fns[node.name] = (params, tuple(names))
        elif isinstance(node, ast.ClassDef):
            is_dc = frozen = has_hash = eq_false = False
            for dec in node.decorator_list:
                d = dotted(dec.func) if isinstance(dec, ast.Call) \
                    else dotted(dec)
                if d and _last(d) == "dataclass":
                    is_dc = True
                    if isinstance(dec, ast.Call):
                        fz = _kw(dec, "frozen")
                        eq = _kw(dec, "eq")
                        frozen = (isinstance(fz, ast.Constant)
                                  and fz.value is True)
                        eq_false = (isinstance(eq, ast.Constant)
                                    and eq.value is False)
            for item in node.body:
                if isinstance(item, ast.FunctionDef) \
                        and item.name == "__hash__":
                    has_hash = True
            if is_dc and not frozen and not eq_false and not has_hash:
                reg.unhashable_dataclasses.add(node.name)


def build_registry(sources: Sequence[Tuple[str, str]]) -> Registry:
    """sources: (path, source_text) pairs."""
    reg = Registry()
    for path, src in sources:
        try:
            tree = ast.parse(src, filename=path)
        except SyntaxError:
            continue
        _registry_scan(tree, reg)
    return reg


# ---------------------------------------------------------------------------
# module-level context: jitted handles, pallas alias, class lock info
# ---------------------------------------------------------------------------


def _pallas_aliases(tree: ast.AST) -> Set[str]:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.endswith("pallas"):
                    out.add(a.asname or a.name)
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name == "pallas":
                    out.add(a.asname or a.name)
    return out or {"pl"}


def _handle_info_from_call(call: ast.Call) -> Optional[HandleInfo]:
    """HandleInfo when `call` builds a jitted/shard_mapped callable."""
    d = dotted(call.func)
    if d is None:
        # jax.jit(f)(...) chains handled at use sites
        return None
    last = _last(d)
    if last in ("jit", "pjit"):
        donate = _tuple_ints(_kw(call, "donate_argnums")
                             or ast.Constant(value=None))
        nums = _tuple_ints(_kw(call, "static_argnums")
                           or ast.Constant(value=None))
        names = _tuple_strs(_kw(call, "static_argnames")
                            or ast.Constant(value=None))
        return HandleInfo(donate=donate, static_nums=nums,
                          static_names=names)
    if last == "shard_map":
        return HandleInfo()
    if HANDLE_MAKER_RE.match(last):
        donate: Tuple[int, ...] = ()
        if last in KNOWN_DONATING_MAKERS:
            dkw = _kw(call, "donate")
            if not (isinstance(dkw, ast.Constant) and dkw.value is False):
                donate = KNOWN_DONATING_MAKERS[last]
        return HandleInfo(donate=donate)
    return None


def _collect_handles(tree: ast.AST) -> Dict[str, HandleInfo]:
    """Names/self-attrs bound to jitted callables anywhere in the module
    (class-attribute bindings are visible across methods)."""
    handles: Dict[str, HandleInfo] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        if not isinstance(value, ast.Call):
            continue
        info = _handle_info_from_call(value)
        if info is None:
            continue
        for t in _assign_targets(node):
            d = dotted(t)
            if d:
                handles[d] = info
    return handles


@dataclasses.dataclass
class LockInfo:
    lock_attrs: Set[str]
    guarded: Set[str]          # self-attrs accessed under any lock


def _with_lock_items(stmt: ast.With, lock_attrs: Set[str]) -> bool:
    for item in stmt.items:
        d = dotted(item.context_expr)
        if d and d.startswith("self.") and d[5:] in lock_attrs:
            return True
        # `with self._lock:` spelled via acquire contexts is out of scope
    return False


def _self_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


def _attr_accesses(node: ast.AST, writes_only: bool = False
                   ) -> List[Tuple[str, ast.AST]]:
    """(attr, node) for self.<attr> accesses in `node`.  Writes are
    Store/AugAssign targets, subscript-stores (`self.x[k] = v`), and
    mutating method calls (`self.x.append(...)`)."""
    out: List[Tuple[str, ast.AST]] = []
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute):
            a = _self_attr(n)
            if a is None:
                continue
            if isinstance(n.ctx, (ast.Store, ast.Del)):
                out.append((a, n))
            elif not writes_only:
                out.append((a, n))
        if isinstance(n, ast.Subscript):
            a = _self_attr(n.value)
            if a is not None and isinstance(n.ctx, (ast.Store, ast.Del)):
                out.append((a, n))
        if isinstance(n, ast.Call):
            fn = n.func
            if isinstance(fn, ast.Attribute) and fn.attr in MUTATORS:
                a = _self_attr(fn.value)
                if a is not None:
                    out.append((a, n))
    return out


def _class_lock_info(cls: ast.ClassDef) -> Optional[LockInfo]:
    lock_attrs: Set[str] = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            d = dotted(node.value.func)
            if d and _last(d) in ("Lock", "RLock"):
                for t in _assign_targets(node):
                    a = _self_attr(t)
                    if a:
                        lock_attrs.add(a)
    if not lock_attrs:
        return None
    guarded: Set[str] = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.With) and _with_lock_items(node, lock_attrs):
            for a, _ in _attr_accesses(node):
                if a not in lock_attrs:
                    guarded.add(a)
    return LockInfo(lock_attrs, guarded)


# ---------------------------------------------------------------------------
# the checker
# ---------------------------------------------------------------------------


class _FileChecker:
    def __init__(self, path: str, source: str, tree: ast.Module,
                 registry: Registry):
        self.path = path
        self.source = source
        self.tree = tree
        self.registry = registry
        self.pl = _pallas_aliases(tree)
        self.handles = _collect_handles(tree)
        self.violations: List[Violation] = []
        # parent links for class/function context
        self._parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent

    def add(self, rule: str, node: ast.AST, msg: str):
        self.violations.append(Violation(
            rule, self.path, getattr(node, "lineno", 0),
            getattr(node, "col_offset", 0), msg))

    # -- dispatch -----------------------------------------------------------
    def run(self) -> List[Violation]:
        self._check_deprecated()
        self._check_pallas()
        self._check_locks()
        self._check_telemetry()
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cls = self._enclosing_class(node)
                _FunctionChecker(self, node,
                                 cls.name if cls else None).run()
        return self.violations

    def _enclosing_class(self, node: ast.AST) -> Optional[ast.ClassDef]:
        cur = self._parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return None      # method-local def: not a method
            cur = self._parents.get(cur)
        return None

    # -- FC-DEPRECATED ------------------------------------------------------
    def _check_deprecated(self):
        for node in ast.walk(self.tree):
            d = dotted(node) if isinstance(node, ast.Attribute) else None
            if d in DEPRECATED_APIS and isinstance(node.ctx, ast.Load):
                self.add("FC-DEPRECATED", node,
                         f"`{d}` was removed from jax; use "
                         f"{DEPRECATED_APIS[d]}")

    # -- FC-PALLAS ----------------------------------------------------------
    def _pl_call(self, node: ast.AST, name: str) -> bool:
        if not isinstance(node, ast.Call):
            return False
        d = dotted(node.func)
        return bool(d) and _root(d) in self.pl and _last(d) == name

    def _check_pallas(self):
        kernel_fns: Set[ast.AST] = set()
        for node in ast.walk(self.tree):
            # pallas_call sites: interpret plumbed + kernel fn collection
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d and _last(d) == "pallas_call":
                    if (_kw(node, "interpret") is None
                            and not _has_double_star(node)):
                        self.add(
                            "FC-PALLAS", node,
                            "pl.pallas_call without `interpret=` — this "
                            "repo plumbs interpret mode through every "
                            "kernel entry point (kernels run interpreted "
                            "on CPU builds)")
                    if node.args:
                        kd = dotted(node.args[0])
                        if kd:
                            kernel_fns.add(_last(kd))
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # program_id under a @pl.when decorator
                under_when = any(
                    isinstance(dec, ast.Call) and self._pl_call(dec, "when")
                    for dec in node.decorator_list)
                if under_when:
                    # decorator expressions evaluate OUTSIDE the region
                    # (`@pl.when(k == 0)` reading program_id in the
                    # condition is the legal top-level idiom) — only the
                    # body runs inside the sub-jaxpr
                    for sub in (s for b in node.body for s in ast.walk(b)):
                        if self._pl_call(sub, "program_id"):
                            self.add(
                                "FC-PALLAS", sub,
                                "pl.program_id inside a pl.when region — "
                                "the interpret-mode evaluator does not "
                                "substitute program ids inside sub-jaxprs;"
                                " read it at the kernel top level and "
                                "close over the value")
                # side effects inside kernel bodies
                is_kernel = node.name in kernel_fns or any(
                    self._pl_call(sub, n) for sub in ast.walk(node)
                    for n in ("program_id", "when", "load", "store"))
                if is_kernel:
                    self._check_kernel_side_effects(node)
            elif isinstance(node, ast.Call):
                # pl.when(cond)(lambda: ... program_id ...)
                if isinstance(node.func, ast.Call) \
                        and self._pl_call(node.func, "when"):
                    for arg in node.args:
                        for sub in ast.walk(arg):
                            if self._pl_call(sub, "program_id"):
                                self.add(
                                    "FC-PALLAS", sub,
                                    "pl.program_id inside a pl.when "
                                    "region — hoist it out")

    def _check_kernel_side_effects(self, fn: ast.AST):
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            d = dotted(sub.func)
            if d is None:
                continue
            if d in ("print", "breakpoint", "input") or (
                    _root(d) in ("time", "datetime")
                    and _last(d) in ("time", "perf_counter", "monotonic",
                                     "now", "today", "utcnow")):
                self.add(
                    "FC-PALLAS", sub,
                    f"side-effecting host call `{d}` inside a Pallas "
                    f"kernel body — it runs once at trace time, never "
                    f"per grid step (use pl.debug_print)")

    # -- FC-TELEMETRY -------------------------------------------------------
    def _is_jit_decorator(self, dec: ast.AST) -> bool:
        d = dotted(dec)
        if d and _last(d) in ("jit", "pjit"):
            return True
        if isinstance(dec, ast.Call):
            d = dotted(dec.func)
            if d and _last(d) in ("jit", "pjit"):
                return True        # @jax.jit(donate_argnums=...) form
            if d and _last(d) == "partial" and dec.args:
                inner = dotted(dec.args[0])
                if inner and _last(inner) in ("jit", "pjit"):
                    return True
        return False

    def _jitted_fn_names(self) -> Set[str]:
        """Function names whose bodies run under jax tracing: decorated
        with jit, passed to a jit()/pjit()/shard_map() call, or inner
        defs returned by a ``make_*``/``jit_*`` step factory (the repo
        convention — the caller always jits the returned callable)."""
        jitted: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(self._is_jit_decorator(dec)
                       for dec in node.decorator_list):
                    jitted.add(node.name)
            elif isinstance(node, ast.Call):
                d = dotted(node.func)
                if d and _last(d) in ("jit", "pjit", "shard_map") \
                        and node.args:
                    ad = dotted(node.args[0])
                    if ad:
                        jitted.add(_last(ad))
        for node in ast.walk(self.tree):
            if isinstance(node, ast.FunctionDef) \
                    and HANDLE_MAKER_RE.match(node.name):
                inner = {s.name for s in ast.walk(node)
                         if isinstance(s, ast.FunctionDef) and s is not node}
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Return) and sub.value is not None:
                        rd = dotted(sub.value)
                        if rd and rd in inner:
                            jitted.add(rd)
        return jitted

    def _check_telemetry(self):
        jitted = self._jitted_fn_names()
        if not jitted:
            return
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in jitted:
                self._check_traced_body(node)

    def _check_traced_body(self, fn: ast.AST):
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            d = dotted(sub.func)
            if d is not None and _last(d) in HOST_CLOCK_CALLS and (
                    _root(d) in ("time", "datetime")
                    or "." not in d):
                self.add(
                    "FC-TELEMETRY", sub,
                    f"host clock `{d}()` inside jit-traced `{fn.name}` — "
                    f"it runs once at trace time and bakes a constant "
                    f"timestamp into the compiled graph; time on the "
                    f"host around the jitted call (XPUTimer.span)")
                continue
            if isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in METRIC_WRITE_ATTRS:
                rd = dotted(sub.func.value)
                if rd and _root(rd) in METRIC_SAFE_ROOTS:
                    continue
                self.add(
                    "FC-TELEMETRY", sub,
                    f"metrics write `.{sub.func.attr}()` inside "
                    f"jit-traced `{fn.name}` — the registry accepts "
                    f"host scalars only and the write executes once at "
                    f"trace time, never per step; record after draining "
                    f"outputs on the host")

    # -- FC-LOCK ------------------------------------------------------------
    def _check_locks(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            info = _class_lock_info(node)
            if info is None:
                continue
            for item in node.body:
                if not isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if item.name.startswith("_"):
                    # private helpers are assumed called under the lock
                    # (__init__ runs before any concurrency exists)
                    continue
                self._check_method_locking(node, item, info)

    def _check_method_locking(self, cls: ast.ClassDef, method: ast.AST,
                              info: LockInfo):
        locked_spans: List[Tuple[int, int]] = []
        for node in ast.walk(method):
            if isinstance(node, ast.With) \
                    and _with_lock_items(node, info.lock_attrs):
                locked_spans.append((node.lineno, node.end_lineno or
                                     node.lineno))

        def under_lock(n: ast.AST) -> bool:
            ln = getattr(n, "lineno", 0)
            return any(a <= ln <= b for a, b in locked_spans)

        for attr, node in _attr_accesses(method, writes_only=True):
            if attr in info.guarded and not under_lock(node):
                self.add(
                    "FC-LOCK", node,
                    f"{cls.name}.{method.name} writes `self.{attr}` "
                    f"without holding the lock that guards it elsewhere "
                    f"(`self.{sorted(info.lock_attrs)[0]}`)")


class _FunctionChecker:
    """Per-function forward pass: loop depth, jit-output taint, donated
    buffers, hot-path conversion checks, jit-in-loop detection."""

    def __init__(self, file_checker: _FileChecker, fn: ast.AST,
                 cls_name: Optional[str]):
        self.fc = file_checker
        self.fn = fn
        self.cls = cls_name
        self.loop_depth = 0
        self.tainted: Set[str] = set()
        self.donated: Dict[str, int] = {}   # name -> line donated
        self.hot = (cls_name in HOT_CLASSES
                    or bool(HOT_FUNC_RE.match(fn.name)))

    # -- entry --------------------------------------------------------------
    def run(self):
        for stmt in self.fn.body:
            self._stmt(stmt)

    # -- statement walk (source order, loop tracking) ------------------------
    def _stmt(self, stmt: ast.AST):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return            # nested defs are visited separately
        if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            header = [stmt.iter] if isinstance(
                stmt, (ast.For, ast.AsyncFor)) else [stmt.test]
            for e in header:
                self._scan_expr(e, stmt)
            self.loop_depth += 1
            for s in stmt.body:
                self._stmt(s)
            self.loop_depth -= 1
            for s in stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, ast.If):
            self._scan_expr(stmt.test, stmt)
            for s in stmt.body + stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._scan_expr(item.context_expr, stmt)
            for s in stmt.body:
                self._stmt(s)
            return
        if isinstance(stmt, ast.Try):
            for field in ("body", "orelse", "finalbody"):
                for s in getattr(stmt, field, []):
                    self._stmt(s)
            for h in stmt.handlers:
                for s in h.body:
                    self._stmt(s)
            return
        # simple statement: run expression checks, then update state
        self._scan_expr(stmt, stmt)
        self._update_state(stmt)

    def _scan_expr(self, expr: ast.AST, stmt: ast.AST):
        """Check calls in one expression (or simple statement)."""
        self._scan_calls(expr, comp_depth=0)
        # donated-buffer reads (any Load of a donated name after donation)
        if self.donated:
            targets = _target_names(stmt)
            reads = _names_in(expr) - targets
            for name in sorted(self.donated):
                if name in reads and not self._is_donation_stmt(stmt, name):
                    self.fc.add(
                        "FC-DONATE", expr,
                        f"`{name}` was donated to a jitted call at line "
                        f"{self.donated[name]} and is read again — the "
                        f"buffer is deleted at dispatch; rebind the "
                        f"result or drop donation")
                    del self.donated[name]

    def _is_donation_stmt(self, stmt: ast.AST, name: str) -> bool:
        """The donating call itself mentions the name as an argument."""
        return getattr(stmt, "lineno", -1) == self.donated.get(name)

    def _scan_calls(self, node: ast.AST, comp_depth: int):
        """Recursive call scan tracking comprehension nesting —
        comprehensions are per-element loops for the host-sync rules,
        but building a bounded handle table `{a: jax.jit(...) for a in
        stages}` before the hot loop is the repo idiom, so they do NOT
        count for the jit-in-loop rule."""
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                             ast.GeneratorExp)):
            comp_depth += 1
        if isinstance(node, ast.Call):
            self._check_call(node, comp_depth)
        for child in ast.iter_child_nodes(node):
            self._scan_calls(child, comp_depth)

    # -- per-call checks ----------------------------------------------------
    def _check_call(self, call: ast.Call, comp_depth: int = 0):
        d = dotted(call.func)
        in_loop = self.loop_depth > 0 or comp_depth > 0

        # FC-RECOMPILE: jit/shard_map built inside a loop (real
        # statement loops only — see _scan_calls on comprehensions)
        if d and _last(d) in ("jit", "pjit", "shard_map") \
                and self.loop_depth > 0:
            self.fc.add(
                "FC-RECOMPILE", call,
                f"`{d}` constructed inside a loop — each iteration builds "
                f"a fresh wrapper with an empty compile cache; hoist it "
                f"out of the loop")

        # FC-RECOMPILE: unhashable values in static positions
        self._check_static_args(call, d)

        # FC-HOSTSYNC: conversions
        if d in CONVERTERS and len(call.args) == 1:
            self._check_conversion(call, call.args[0], d, in_loop)
        elif d and _last(d) in ("asarray", "array") \
                and _root(d) in ("np", "numpy") and call.args:
            if self._is_tainted(call.args[0]) and in_loop:
                self.fc.add(
                    "FC-HOSTSYNC", call,
                    "np.asarray on a jitted-step output inside a loop "
                    "blocks on the device per iteration — drain once "
                    "via jax.device_get at the loop boundary")
        elif isinstance(call.func, ast.Attribute) \
                and call.func.attr == "item" and not call.args:
            if self._is_tainted(call.func.value) and in_loop:
                self.fc.add(
                    "FC-HOSTSYNC", call,
                    ".item() on a jitted-step output inside a loop is a "
                    "per-iteration host sync — batch the drain")

    def _check_conversion(self, call: ast.Call, arg: ast.AST, conv: str,
                          in_loop: bool):
        if self._is_tainted(arg) and in_loop:
            self.fc.add(
                "FC-HOSTSYNC", call,
                f"{conv}() on a value flowing from a jitted step inside "
                f"a per-step loop — an undrained device metric blocks "
                f"the dispatch pipeline every iteration; accumulate and "
                f"drain via jax.device_get every N steps")
            return
        # hot-path form: eager conversion of a fresh call result
        # (the PR-4 `float(sched(i))` hidden LR sync)
        if self.hot and in_loop and isinstance(arg, ast.Call):
            ad = dotted(arg.func)
            if ad is None:
                return
            if _last(ad) in HOST_SAFE_LAST or _root(ad) in HOST_SAFE_ROOTS:
                return
            if self._is_cleansed(arg):
                return
            self.fc.add(
                "FC-HOSTSYNC", call,
                f"{conv}({ad}(...)) inside a hot per-step loop — if "
                f"`{ad}` computes with jnp this is a hidden per-step "
                f"device sync (evaluate host-side, e.g. a .host() "
                f"variant, or drain at the loop boundary)")

    def _check_static_args(self, call: ast.Call, d: Optional[str]):
        reg = self.fc.registry
        info: Optional[HandleInfo] = None
        params: Tuple[str, ...] = ()
        static_names: Tuple[str, ...] = ()
        static_nums: Tuple[int, ...] = ()
        if d is not None and d in self.fc.handles:
            info = self.fc.handles[d]
            static_nums, static_names = info.static_nums, info.static_names
        elif isinstance(call.func, ast.Call):
            inner = _handle_info_from_call(call.func)
            if inner is not None:
                static_nums = inner.static_nums
                static_names = inner.static_names
        elif d is not None and _last(d) in reg.static_fns:
            params, static_names = reg.static_fns[_last(d)]
        if not (static_nums or static_names):
            return

        def flag(node: ast.AST, what: str, where: str):
            self.fc.add(
                "FC-RECOMPILE", node,
                f"{what} passed as static arg {where} — unhashable or "
                f"freshly constructed every call, so the jit cache "
                f"misses and the step recompiles")

        for i, arg in enumerate(call.args):
            is_static = i in static_nums or (
                params and i < len(params) and params[i] in static_names)
            if not is_static:
                continue
            kind = _is_unhashable_literal(arg)
            if kind:
                flag(arg, f"{kind} literal", f"#{i}")
            elif isinstance(arg, ast.Call):
                cd = dotted(arg.func)
                if cd and _last(cd) in reg.unhashable_dataclasses:
                    flag(arg, f"fresh `{_last(cd)}` instance (dataclass "
                         f"without frozen=True/__hash__)", f"#{i}")
        for k in call.keywords:
            if k.arg is None or k.arg not in static_names:
                continue
            kind = _is_unhashable_literal(k.value)
            if kind:
                flag(k.value, f"{kind} literal", f"`{k.arg}=`")
            elif isinstance(k.value, ast.Call):
                cd = dotted(k.value.func)
                if cd and _last(cd) in reg.unhashable_dataclasses:
                    flag(k.value, f"fresh `{_last(cd)}` instance "
                         f"(dataclass without frozen=True/__hash__)",
                         f"`{k.arg}=`")

    # -- taint machinery ----------------------------------------------------
    def _is_cleansed(self, node: ast.AST) -> bool:
        """Expression routed through jax.device_get (an explicit drain)."""
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                cd = dotted(n.func)
                if cd and _last(cd) == "device_get":
                    return True
        return False

    def _is_tainted(self, node: ast.AST) -> bool:
        if self._is_cleansed(node):
            return False
        return bool(_names_in(node) & self.tainted)

    def _update_state(self, stmt: ast.AST):
        if not isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            return
        value = getattr(stmt, "value", None)
        if value is None:
            return
        targets = _target_names(stmt)
        # donation: calling a donating handle consumes its donated args
        for node in ast.walk(value):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            if d is None or d not in self.fc.handles:
                continue
            for i in self.fc.handles[d].donate:
                if i < len(node.args):
                    an = dotted(node.args[i])
                    if an and an not in targets:
                        self.donated[an] = getattr(stmt, "lineno", 0)
                    elif an in targets:
                        self.donated.pop(an, None)
        # taint: results of jitted-handle calls, and propagation
        tainted_value = False
        if isinstance(value, ast.Call):
            d = dotted(value.func)
            if d is not None and d in self.fc.handles:
                tainted_value = True
            elif isinstance(value.func, ast.Call):
                if _handle_info_from_call(value.func) is not None:
                    tainted_value = True
        if not tainted_value and self._is_tainted(value):
            tainted_value = True
        for t in targets:
            if tainted_value:
                self.tainted.add(t)
            else:
                self.tainted.discard(t)
            self.donated.pop(t, None)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def check_source(source: str, path: str = "<string>",
                 registry: Optional[Registry] = None) -> List[Violation]:
    """All violations in one source blob (suppressed ones included, with
    `.suppressed` set — filter on it for enforcement)."""
    registry = registry or build_registry([(path, source)])
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Violation("FC-SYNTAX", path, e.lineno or 0, e.offset or 0,
                          f"syntax error: {e.msg}")]
    per_line, file_wide = _suppressions(source)
    raw = _FileChecker(path, source, tree, registry).run()
    out = []
    for v in raw:
        disabled = v.rule in file_wide or v.rule in per_line.get(v.line,
                                                                 set())
        out.append(dataclasses.replace(v, suppressed=disabled))
    return sorted(out, key=lambda v: (v.line, v.col, v.rule))


def check_file(path, registry: Optional[Registry] = None) -> List[Violation]:
    p = Path(path)
    return check_source(p.read_text(), str(p), registry)


def iter_py_files(paths: Sequence, exclude: Sequence[str] = ()):
    for root in paths:
        root = Path(root)
        files = [root] if root.is_file() else sorted(root.rglob("*.py"))
        for f in files:
            s = str(f)
            if any(e in s for e in exclude):
                continue
            yield f


def check_paths(paths: Sequence, exclude: Sequence[str] = ()
                ) -> List[Violation]:
    """Two-phase check: build the cross-file registry (static-arg'd jit
    functions, unhashable dataclasses), then lint every file."""
    files = list(iter_py_files(paths, exclude))
    sources = [(str(f), f.read_text()) for f in files]
    registry = build_registry(sources)
    out: List[Violation] = []
    for path, src in sources:
        out.extend(check_source(src, path, registry))
    return out
