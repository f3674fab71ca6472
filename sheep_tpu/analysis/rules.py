"""The sheeplint rule classes (ISSUE 6; ``h2d`` added by ISSUE 12).

Each rule is an AST pass over one file, sharing the cross-file
:class:`~sheep_tpu.analysis.index.PackageIndex`. The analyses are
deliberately HEURISTIC — linear statement-order taint with no fixpoint,
no inter-procedural flow — tuned so that (a) every rule fires on the
canonical bad pattern it exists for (pinned by tests/test_sheeplint.py
fixtures) and (b) the current package audits clean without baselining,
with the legitimate sync points reading through ``obs.pull`` (which
opens the runtime sanitizer's ``sync_ok()`` window and names the pull
on the profiler's trace) or carrying ``# sheeplint: sync-ok`` pragmas
that double as documentation and as the map of where those windows
belong. A heuristic this
shape catches the regression that matters — someone inlining an
``int(sv[0])`` into a dispatch loop — without drowning the gate in
false positives that would teach people to sprinkle pragmas blindly.

Rules:

- **sync** — implicit device->host syncs: ``int()``/``float()``/
  ``bool()``/``.item()``/``.tolist()``/``np.asarray()`` applied to, or
  ``if``/``while``/``assert`` branching on, a value that flows from a
  jit'd call (or a ``jax.Array``-annotated parameter). One stray sync
  in the dispatch path reverts the in-flight pipeline to lockstep
  (PR 3's invariant).
- **donate** — use-after-donate: a variable passed at a donated
  position (``donate_argnums``, or any ``*_donated`` callee) is dead;
  reading it later is the live bug class
  ``fold_segments_batch_pos_donated`` introduced.
- **jit** — hygiene: jit construction inside a loop (recompilation per
  iteration), non-tuple ``static_argnums``/``static_argnames``
  literals, and Python branching on traced values inside a jit'd
  function (trace-time ConcretizationTypeError, or worse, silent
  specialization).
- **resource** — balance: a ``prefetch``/``prefetch_batched``/
  ``Prefetcher`` acquired without a guaranteed release (``with``,
  immediate ``return``, or a ``.close()`` on the name somewhere in the
  function), an ``obs.begin``/``.begin`` span with no ``.end()`` on
  any path, ``obs.span`` constructed outside a ``with``, and counter
  registries mutated by subscript instead of inc/gauge/absorb.
- **lock** — thread-shared state: in a class owning a
  ``threading.Lock``, attributes written under the lock somewhere must
  be written under it everywhere (the MetricsWriter/heartbeat
  precedent).
- **h2d** — blocking host->device staging on per-chunk hot paths:
  ``jnp.asarray``/``jnp.array``/``jax.device_put`` of a host value
  inside a loop (the synchronous-upload shape the staged H2D ring
  removed, ISSUE 12); designed windows carry ``# sheeplint: h2d-ok``.
- **fold** — the resident delta-fold path (ISSUE 19): inside a
  ``*fold_delta*``/``*move_rescore*`` function, constructing a fold
  pipeline or a jit (per-EPOCH recompile — the cached ``_update_pipe``
  / ``_MOVE_RESCORE_CACHE`` helpers exist so repeat epochs reuse every
  compiled program) and per-CHUNK host pulls inside a loop
  (``np.asarray``/``.item()``/``.tolist()``/``.block_until_ready()`` —
  the O(Δ) epoch's designed shape is ONE pull after the fold
  converges); designed windows carry ``# sheeplint: fold-ok``.
- **spill** — out-of-core discipline (ISSUE 20): full materialization
  of an mmap CSR region (``np.asarray``/``np.array`` over a bare
  ``indices``/``indptr`` attribute or a whole-region ``[:]`` slice of
  one — the disk tier pulled entirely into host memory, defeating the
  O(chunk) working-set contract; element/range subscripts stay
  O(slice) and are fine), and a per-chunk device upload inside a loop
  (``jax.device_put``/``jnp.asarray`` of a ``pad_chunk``/
  ``device_chunk`` product) that bypasses the residency manager — HBM
  the budget model cannot see or spill; designed windows carry
  ``# sheeplint: spill-ok``.
"""

from __future__ import annotations

import ast

from sheep_tpu.analysis.core import Finding, pragma_lines, suppressed
from sheep_tpu.analysis.index import PackageIndex, _jit_call_info

#: attribute reads that yield host metadata, not device values
METADATA_ATTRS = {
    "shape", "ndim", "dtype", "size", "nbytes", "itemsize", "sharding",
    "device", "devices", "is_deleted", "addressable_shards", "weak_type",
}

#: module roots whose calls produce device arrays
DEVICE_MODULES = {"jnp", "lax"}

#: receiver methods that fold a tainted argument into the receiver
CONTAINER_MUTATORS = {"append", "appendleft", "add", "insert", "extend",
                      "update", "put"}

HOST_CONVERTERS = {"int", "float", "bool", "complex"}

PREFETCH_FNS = {"prefetch", "prefetch_batched", "Prefetcher"}

LOCK_MUTATING_METHODS = {
    "write", "writelines", "flush", "close", "append", "extend",
    "insert", "pop", "popleft", "clear", "update", "add", "remove",
    "discard", "put", "emit", "send", "truncate", "seek",
}


def _terminal(node) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def _root(node) -> str:
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Call)):
        node = node.func if isinstance(node, ast.Call) else node.value
    return node.id if isinstance(node, ast.Name) else ""


def _is_np_pull(call: ast.Call) -> bool:
    """np.asarray(x) / np.array(x) — the explicit pull form."""
    fn = call.func
    return (isinstance(fn, ast.Attribute) and fn.attr in ("asarray", "array")
            and _root(fn) in ("np", "numpy"))


class RuleContext:
    def __init__(self, path: str, source: str, tree: ast.Module,
                 index: PackageIndex):
        self.path = path
        self.tree = tree
        self.index = index
        self.pragmas = pragma_lines(source)
        self.findings: list = []

    def add(self, rule: str, severity: str, node, message: str) -> None:
        f = Finding(rule=rule, severity=severity, path=self.path,
                    line=getattr(node, "lineno", 0), message=message)
        span = (getattr(node, "lineno", 0),
                getattr(node, "end_lineno", None))
        if not suppressed(f, self.pragmas, span):
            self.findings.append(f)


def _decorated_jit(fn) -> tuple:
    """(is_jit, static_param_names) for a FunctionDef's decorators."""
    static: set = set()
    is_jit = False
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call):
            ok, _ = _jit_call_info(dec)
            if not ok:
                continue
            is_jit = True
            params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
            for kw in dec.keywords:
                if kw.arg == "static_argnames" and \
                        isinstance(kw.value, (ast.Tuple, ast.List)):
                    static |= {e.value for e in kw.value.elts
                               if isinstance(e, ast.Constant)
                               and isinstance(e.value, str)}
                elif kw.arg == "static_argnums":
                    vals = kw.value.elts \
                        if isinstance(kw.value, (ast.Tuple, ast.List)) \
                        else [kw.value]
                    for e in vals:
                        if isinstance(e, ast.Constant) \
                                and isinstance(e.value, int) \
                                and e.value < len(params):
                            static.add(params[e.value])
        elif _terminal(dec) == "jit":
            is_jit = True
    return is_jit, static


# ---------------------------------------------------------------------------
# sync + jit-branching + donate: one linear taint pass per scope
# ---------------------------------------------------------------------------

class _TaintScope:
    """Linear statement-order taint over one function (or module) body.

    ``in_jit`` switches the sink rule: outside jit, a host conversion /
    branch on a tainted value is a **sync** finding; inside a jit'd
    function the same shape is a **jit** finding (it does not sync —
    it breaks or silently specializes the trace)."""

    def __init__(self, ctx: RuleContext, in_jit: bool = False,
                 taint=None, jit_aliases=None, donating_aliases=None):
        self.ctx = ctx
        self.in_jit = in_jit
        self.taint = set(taint or ())
        self.jit_aliases = set(jit_aliases or ())
        self.donating_aliases = set(donating_aliases or ())
        self.dead: dict = {}  # name -> donating callee (use-after-donate)
        # per-key taint for dicts built from literals with constant
        # string keys: the dispatch drivers keep mixed host/device
        # state in one dict ({"tipP": <device>, "flushing": False}),
        # and blanket container taint would flag every host-field read
        self.key_taint: dict = {}  # name -> set of tainted keys

    # -- callee classification ---------------------------------------------
    def _callee_jit(self, call: ast.Call) -> bool:
        name = _terminal(call.func)
        if name in self.jit_aliases or self.ctx.index.is_jit(name):
            return True
        return _root(call.func) in DEVICE_MODULES

    def _callee_donating(self, call: ast.Call):
        name = _terminal(call.func)
        if name in self.donating_aliases:
            return name, None
        if self.ctx.index.is_donating(name):
            return name, self.ctx.index.donated_positions(name)
        return None, ()

    # -- taint of an expression --------------------------------------------
    def tainted(self, node) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.taint
        if isinstance(node, ast.Attribute):
            if node.attr in METADATA_ATTRS:
                return False
            return self.tainted(node.value)
        if isinstance(node, ast.Subscript):
            if isinstance(node.value, ast.Name) \
                    and node.value.id in self.key_taint \
                    and isinstance(node.slice, ast.Constant):
                return node.slice.value in self.key_taint[node.value.id]
            return self.tainted(node.value)
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id in HOST_CONVERTERS:
                return False  # host converter launders (and is a sink)
            if _is_np_pull(node):
                return False
            if self._callee_jit(node):
                return True
            # a method on a tainted receiver stays device-side
            # (P.astype(...), table.at[...].min(...))
            if isinstance(fn, ast.Attribute) and self.tainted(fn):
                return True
            return False
        if isinstance(node, ast.BinOp):
            return self.tainted(node.left) or self.tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.tainted(node.operand)
        if isinstance(node, ast.Compare):
            return self.tainted(node.left) or \
                any(self.tainted(c) for c in node.comparators)
        if isinstance(node, ast.BoolOp):
            return any(self.tainted(v) for v in node.values)
        if isinstance(node, ast.IfExp):
            return self.tainted(node.body) or self.tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.tainted(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(v is not None and self.tainted(v)
                       for v in node.values)
        if isinstance(node, ast.Starred):
            return self.tainted(node.value)
        if isinstance(node, ast.NamedExpr):
            return self.tainted(node.value)
        return False

    # -- sinks --------------------------------------------------------------
    def _sync_rule(self):
        return ("jit", "error") if self.in_jit else ("sync", "error")

    def scan(self, expr) -> None:
        """Flag sink patterns in one expression tree (nested function
        bodies excluded — they get their own scopes)."""
        if expr is None:
            return
        for node in ast.walk(expr):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not isinstance(node, ast.Call):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in self.dead:
                    self.ctx.add(
                        "donate", "error", node,
                        f"'{node.id}' was donated to "
                        f"{self.dead[node.id]}() and is dead; reading "
                        f"it is use-after-donate (rebind it, or drop "
                        f"the donation)")
                    del self.dead[node.id]
                continue
            fn = node.func
            rule, sev = self._sync_rule()
            if isinstance(fn, ast.Name) and fn.id in HOST_CONVERTERS \
                    and len(node.args) == 1 \
                    and self.tainted(node.args[0]):
                self.ctx.add(
                    rule, sev, node,
                    f"{fn.id}() on a value from a jit'd call "
                    + ("inside a jit'd function (breaks or "
                       "specializes the trace)" if self.in_jit else
                       "forces an implicit device->host sync; pull "
                       "via obs.pull(label, x) at a designed sync point "
                       "or keep it a future"))
            elif _is_np_pull(node) and node.args \
                    and self.tainted(node.args[0]):
                self.ctx.add(
                    rule, sev, node,
                    "np.asarray/np.array on a value from a jit'd call "
                    "is a device->host pull; make the designed sync "
                    "point an obs.pull(label, x), or annotate it with "
                    "'# sheeplint: sync-ok' (and wrap it in "
                    "sanitize.sync_ok() on guarded paths)")
            elif isinstance(fn, ast.Attribute) \
                    and fn.attr in ("item", "tolist") \
                    and self.tainted(fn.value):
                self.ctx.add(
                    rule, sev, node,
                    f".{fn.attr}() on a value from a jit'd call "
                    "forces an implicit device->host sync")

    # -- assignment ---------------------------------------------------------
    def _bind(self, target, is_tainted: bool) -> None:
        if isinstance(target, ast.Name):
            self.dead.pop(target.id, None)
            self.key_taint.pop(target.id, None)
            if is_tainted:
                self.taint.add(target.id)
            else:
                self.taint.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt, is_tainted)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, is_tainted)
        elif isinstance(target, ast.Subscript):
            base = target.value
            if isinstance(base, ast.Name) and base.id in self.key_taint \
                    and isinstance(target.slice, ast.Constant):
                if is_tainted:
                    self.key_taint[base.id].add(target.slice.value)
                else:
                    self.key_taint[base.id].discard(target.slice.value)
            elif is_tainted and isinstance(base, ast.Name):
                # storing a tainted value into a container taints it
                self.taint.add(base.id)

    def _track_dict(self, target, value) -> None:
        if isinstance(target, ast.Name) and isinstance(value, ast.Dict) \
                and all(isinstance(k, ast.Constant) for k in value.keys):
            self.key_taint[target.id] = {
                k.value for k, v in zip(value.keys, value.values)
                if v is not None and self.tainted(v)}

    def _mark_donated(self, call: ast.Call) -> None:
        name, positions = self._callee_donating(call)
        if name is None:
            return
        if any(isinstance(a, ast.Starred) for a in call.args):
            return  # positions unresolvable
        args = call.args
        idxs = range(len(args)) if positions is None else positions
        for i in idxs:
            if i < len(args) and isinstance(args[i], ast.Name):
                self.dead[args[i].id] = name

    def _maybe_alias(self, target, value) -> None:
        """``fold = donated_fn if cond else plain_fn`` — record the
        alias so calls through it keep jit/donate semantics."""
        names = []
        if isinstance(value, (ast.Name, ast.Attribute)):
            names = [_terminal(value)]
        elif isinstance(value, ast.IfExp):
            names = [_terminal(value.body), _terminal(value.orelse)]
        if not names or not isinstance(target, ast.Name):
            return
        if any(self.ctx.index.is_jit(n) for n in names if n):
            self.jit_aliases.add(target.id)
        if any(self.ctx.index.is_donating(n) for n in names if n):
            self.donating_aliases.add(target.id)

    # -- statements ---------------------------------------------------------
    def exec_body(self, stmts) -> None:
        for st in stmts:
            self.exec_stmt(st)

    def _donate_in(self, expr) -> None:
        if expr is None:
            return
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                self._mark_donated(node)

    def exec_stmt(self, st) -> None:
        ctx = self.ctx
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._run_nested(st)
            return
        if isinstance(st, ast.ClassDef):
            for sub in st.body:
                self.exec_stmt(sub)
            return
        if isinstance(st, ast.Assign):
            self.scan(st.value)
            self._donate_in(st.value)
            is_t = self.tainted(st.value)
            for tgt in st.targets:
                self._maybe_alias(tgt, st.value)
                self._bind(tgt, is_t)
                self._track_dict(tgt, st.value)
        elif isinstance(st, ast.AnnAssign):
            self.scan(st.value)
            self._donate_in(st.value)
            if st.value is not None:
                self._bind(st.target, self.tainted(st.value))
        elif isinstance(st, ast.AugAssign):
            self.scan(st.value)
            self._donate_in(st.value)
            if isinstance(st.target, ast.Name):
                if self.tainted(st.value):
                    self.taint.add(st.target.id)
        elif isinstance(st, ast.Expr):
            self.scan(st.value)
            self._donate_in(st.value)
            v = st.value
            if isinstance(v, ast.Call) and isinstance(v.func, ast.Attribute):
                # container.append(tainted, ...) taints the container
                if v.func.attr in CONTAINER_MUTATORS \
                        and isinstance(v.func.value, ast.Name) \
                        and any(self.tainted(a) for a in v.args):
                    self.taint.add(v.func.value.id)
        elif isinstance(st, (ast.Return, ast.Delete, ast.Raise)):
            for child in ast.iter_child_nodes(st):
                self.scan(child)
                self._donate_in(child)
        elif isinstance(st, ast.Assert):
            self.scan(st.test)
            if self.tainted(st.test):
                rule, sev = self._sync_rule()
                # anchor to the test expression, not the statement: the
                # statement's line span covers the whole body, so an
                # unrelated pragma inside it would suppress this finding
                ctx.add(rule, sev, st.test,
                        "assert on a value from a jit'd call "
                        + ("inside a jit'd function" if self.in_jit
                           else "forces an implicit device->host sync"))
        elif isinstance(st, (ast.If, ast.While)):
            self.scan(st.test)
            self._donate_in(st.test)
            if self.tainted(st.test):
                rule, sev = self._sync_rule()
                kw = "while" if isinstance(st, ast.While) else "if"
                ctx.add(rule, sev, st.test,  # test, not st: see Assert
                        f"Python `{kw}` on a value from a jit'd call "
                        + ("inside a jit'd function (trace-time "
                           "branch)" if self.in_jit else
                           "forces an implicit device->host sync; "
                           "read it at an annotated sync point first"))
            self.exec_body(st.body)
            self.exec_body(st.orelse)
        elif isinstance(st, ast.For):
            self.scan(st.iter)
            self._donate_in(st.iter)
            self._bind(st.target, self.tainted(st.iter))
            self.exec_body(st.body)
            self.exec_body(st.orelse)
        elif isinstance(st, ast.With):
            for item in st.items:
                self.scan(item.context_expr)
                self._donate_in(item.context_expr)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars,
                               self.tainted(item.context_expr))
            self.exec_body(st.body)
        elif isinstance(st, ast.Try):
            self.exec_body(st.body)
            for h in st.handlers:
                self.exec_body(h.body)
            self.exec_body(st.orelse)
            self.exec_body(st.finalbody)
        # Import / Global / Pass / Break / Continue: nothing to do

    def _run_nested(self, fn) -> None:
        is_jit, static = _decorated_jit(fn)
        sub = _TaintScope(
            self.ctx,
            in_jit=is_jit or self.in_jit,
            taint=self.taint,  # free-variable approximation
            jit_aliases=self.jit_aliases,
            donating_aliases=self.donating_aliases)
        sub.key_taint = {k: set(v) for k, v in self.key_taint.items()}
        if is_jit:
            params = [a.arg for a in fn.args.posonlyargs + fn.args.args
                      + fn.args.kwonlyargs]
            sub.taint |= {p for p in params if p not in static}
        else:
            for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs:
                # jax.Array-annotated params are device values; np.ndarray
                # (and unannotated) params are host side
                ann = a.annotation
                if ann is not None and _terminal(ann) == "Array" \
                        and (isinstance(ann, ast.Name)
                             or _root(ann) in ("jax", "jnp")):
                    sub.taint.add(a.arg)
                else:
                    sub.taint.discard(a.arg)
        sub.exec_body(fn.body)


def check_sync_donate(ctx: RuleContext) -> None:
    scope = _TaintScope(ctx, in_jit=False)
    scope.exec_body(ctx.tree.body)


# ---------------------------------------------------------------------------
# jit hygiene: construction-in-loop + non-tuple static literals
# ---------------------------------------------------------------------------

class _JitHygiene(ast.NodeVisitor):
    def __init__(self, ctx: RuleContext):
        self.ctx = ctx
        self.loop_depth = 0

    def _check_call(self, node: ast.Call) -> None:
        is_jit, _ = _jit_call_info(node)
        if not is_jit:
            return
        if self.loop_depth > 0:
            self.ctx.add(
                "jit", "warning", node,
                "jax.jit constructed inside a loop: every iteration "
                "builds (and likely recompiles) a fresh program — "
                "hoist it or cache per static key")
        for kw in node.keywords:
            if kw.arg in ("static_argnums", "static_argnames") \
                    and isinstance(kw.value, (ast.List, ast.Set,
                                              ast.Dict)):
                self.ctx.add(
                    "jit", "warning", kw.value,
                    f"{kw.arg} given a non-tuple literal; use a tuple "
                    "(hashable, order-stable) so the jit cache key is "
                    "well-defined")

    def visit_Call(self, node):
        self._check_call(node)
        self.generic_visit(node)

    def _loop(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_While = _loop
    visit_ListComp = visit_SetComp = visit_DictComp = _loop
    visit_GeneratorExp = _loop


def check_jit_hygiene(ctx: RuleContext) -> None:
    _JitHygiene(ctx).visit(ctx.tree)


# ---------------------------------------------------------------------------
# resource balance
# ---------------------------------------------------------------------------

def _collect_method_receivers(fn, method: str) -> set:
    """Names X with an ``X.<method>(...)`` call anywhere in fn's
    subtree (nested defs included — closures may release for the
    enclosing scope, e.g. the rolling dispatch spans)."""
    out = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == method \
                and isinstance(node.func.value, ast.Name):
            out.add(node.func.value.id)
    return out


def _assigned_names(target) -> list:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out = []
        for e in target.elts:
            out.extend(_assigned_names(e))
        return out
    return []


def _immediate_stmts(scope):
    """Statements of ``scope`` excluding nested function bodies (those
    are their own scopes; `with`-acquired resources never reach here
    because only Assign/Expr statements are classified)."""
    out: list = []

    def rec(stmts):
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
                continue
            out.append(st)
            for fld in ("body", "orelse", "finalbody"):
                sub = getattr(st, fld, None)
                if isinstance(sub, list):
                    rec(sub)
            for h in getattr(st, "handlers", ()):
                rec(h.body)

    rec(scope.body)
    return out


def check_resources(ctx: RuleContext) -> None:
    scopes = [ctx.tree] + [
        n for n in ast.walk(ctx.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for scope in scopes:
        # releases may live in nested closures acting for this scope
        # (the rolling dispatch spans), so collect over the full subtree
        closers = _collect_method_receivers(scope, "close")
        enders = _collect_method_receivers(scope, "end")
        for st in _immediate_stmts(scope):
            if isinstance(st, ast.Assign) and isinstance(st.value, ast.Call):
                name = _terminal(st.value.func)
                targets = []
                for t in st.targets:
                    targets.extend(_assigned_names(t))
                if name in PREFETCH_FNS:
                    if not any(t in closers for t in targets):
                        ctx.add(
                            "resource", "error", st.value,
                            f"{name}(...) bound to "
                            f"{'/'.join(targets) or 'a non-name target'}"
                            " with no close() on any path: an "
                            "abandoning consumer leaks the worker "
                            "thread — use `with ... as pf:` or "
                            "close() in a finally")
                elif name == "begin":
                    if not any(t in enders for t in targets):
                        ctx.add(
                            "resource", "error", st.value,
                            "span begun but never .end()ed in this "
                            "function: the trace reports it UNCLOSED "
                            "on every run, not just dead ones — end "
                            "it, or use `with obs.span(...)`")
            elif isinstance(st, ast.Expr) and isinstance(st.value, ast.Call):
                name = _terminal(st.value.func)
                if name in PREFETCH_FNS:
                    ctx.add("resource", "error", st.value,
                            f"{name}(...) result discarded: the worker "
                            "thread starts and nothing can ever stop it")
                elif name == "begin":
                    ctx.add("resource", "error", st.value,
                            "span begun and discarded: nothing can "
                            "end it")
            if isinstance(st, (ast.Assign, ast.AugAssign)):
                tgts = st.targets if isinstance(st, ast.Assign) \
                    else [st.target]
                for tgt in tgts:
                    if isinstance(tgt, ast.Subscript) \
                            and isinstance(tgt.value, ast.Attribute) \
                            and tgt.value.attr == "counters":
                        ctx.add(
                            "resource", "warning", st,
                            "counters mutated by subscript outside "
                            "the CounterRegistry API; use inc()/"
                            "gauge()/absorb() so heartbeat snapshots "
                            "and span deltas stay consistent")


# ---------------------------------------------------------------------------
# h2d staging (ISSUE 12): blocking host->device uploads on per-chunk
# hot paths
# ---------------------------------------------------------------------------

class _H2DStaging(ast.NodeVisitor):
    """Flag ``jnp.asarray``/``jnp.array``/``jax.device_put`` calls
    lexically inside a ``for``/``while`` loop — the per-chunk hot-path
    shape whose synchronous H2D transfer the staged ring
    (utils/prefetch.H2DRing) replaced, and the regression class this
    rule keeps from creeping back. Device-valued arguments move no host
    bytes (a jnp call on a jnp/lax result is the *sync* rule's domain,
    not this one's), so the obvious ones are skipped; the designated
    windows — the ring's own issue point, per-attempt resume uploads,
    measurement probes — carry ``# sheeplint: h2d-ok``, the same
    reviewed-whitelist convention as ``sync-ok``."""

    def __init__(self, ctx: RuleContext):
        self.ctx = ctx
        self.loop_depth = 0

    def _loop(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_While = visit_AsyncFor = _loop

    def _def(self, node):
        # a nested function's body does not execute per iteration of
        # the enclosing loop; it gets its own scan at depth 0
        depth, self.loop_depth = self.loop_depth, 0
        self.generic_visit(node)
        self.loop_depth = depth

    visit_FunctionDef = visit_AsyncFunctionDef = visit_Lambda = _def

    @staticmethod
    def _device_valued(arg) -> bool:
        return isinstance(arg, ast.Call) and _root(arg.func) in DEVICE_MODULES

    def visit_Call(self, node):
        if self.loop_depth > 0:
            fn = node.func
            if isinstance(fn, ast.Attribute):
                root = _root(fn)
                h2d = (fn.attr in ("asarray", "array") and root == "jnp") \
                    or (fn.attr == "device_put" and root == "jax")
                if h2d and node.args \
                        and not self._device_valued(node.args[0]):
                    self.ctx.add(
                        "h2d", "error", node,
                        f"{root}.{fn.attr}() inside a loop issues a "
                        "host->device transfer on the hot path at the "
                        "moment the value is needed — stage it ahead "
                        "through utils/prefetch.H2DRing (or a device "
                        "stream), or annotate a designed window with "
                        "'# sheeplint: h2d-ok'")
        self.generic_visit(node)


def check_h2d(ctx: RuleContext) -> None:
    _H2DStaging(ctx).visit(ctx.tree)


# ---------------------------------------------------------------------------
# delta fold path (ISSUE 19): per-epoch recompiles and per-chunk host
# syncs in the resident update fold
# ---------------------------------------------------------------------------

#: the multi-device fold pipelines — constructing one compiles programs
FOLD_PIPELINE_CTORS = {"ShardedPipeline", "BigVPipeline"}

#: method pulls that synchronize device work onto the host
FOLD_PULL_METHODS = {"item", "tolist", "block_until_ready"}


class _FoldPath(ast.NodeVisitor):
    """Scan ``*fold_delta*`` / ``*move_rescore*`` function bodies — the
    per-epoch resident update path (ISSUE 19). Two regression classes:

    - a fold pipeline constructed (or a jit built) inline re-COMPILES
      every epoch; the epoch cost then is compile wall, not the O(Δ)
      fold — the cached ``_update_pipe`` / ``_MOVE_RESCORE_CACHE``
      helpers are the blessed shape;
    - a host pull (``np.asarray``/``.item()``/``.tolist()``/
      ``.block_until_ready()``) inside a chunk loop serializes the
      lockstep fold per chunk; the designed shape pulls ONCE after the
      fold converges (those single pulls sit at loop depth 0, or carry
      ``# sheeplint: fold-ok``)."""

    def __init__(self, ctx: RuleContext):
        self.ctx = ctx
        self.loop_depth = 0
        self.in_fold = False

    def _loop(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_While = visit_AsyncFor = _loop

    def _def(self, node):
        name = getattr(node, "name", "")
        # _make_* builders are the cached-construction fix this rule
        # recommends — the one place a compile belongs
        on_path = ("fold_delta" in name or "move_rescore" in name) \
            and not name.startswith("_make")
        fold, self.in_fold = self.in_fold, self.in_fold or on_path
        # a nested function's body does not execute per iteration of
        # the enclosing loop; it gets its own scan at depth 0
        depth, self.loop_depth = self.loop_depth, 0
        self.generic_visit(node)
        self.loop_depth = depth
        self.in_fold = fold

    visit_FunctionDef = visit_AsyncFunctionDef = _def

    def visit_Lambda(self, node):
        depth, self.loop_depth = self.loop_depth, 0
        self.generic_visit(node)
        self.loop_depth = depth

    def visit_Call(self, node):
        if self.in_fold:
            term = _terminal(node.func)
            is_jit, _ = _jit_call_info(node)
            if term in FOLD_PIPELINE_CTORS or is_jit:
                self.ctx.add(
                    "fold", "error", node,
                    f"{term}(...) constructed on the delta fold path: "
                    "every epoch recompiles its programs — build it "
                    "once in a cached helper (the _update_pipe "
                    "convention), or annotate a designed window with "
                    "'# sheeplint: fold-ok'")
            elif self.loop_depth > 0:
                pull = _is_np_pull(node) or (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in FOLD_PULL_METHODS)
                if pull:
                    self.ctx.add(
                        "fold", "error", node,
                        "host pull inside a loop on the delta fold "
                        "path serializes the lockstep fold per chunk "
                        "— pull ONCE after the fold converges, or "
                        "annotate a designed window with "
                        "'# sheeplint: fold-ok'")
        self.generic_visit(node)


def check_fold(ctx: RuleContext) -> None:
    _FoldPath(ctx).visit(ctx.tree)


# ---------------------------------------------------------------------------
# out-of-core residency discipline (ISSUE 20)
# ---------------------------------------------------------------------------

#: mmap-backed CSR region attributes (io/csr.py CsrView)
CSR_REGION_ATTRS = {"indices", "indptr", "_indices", "_indptr"}

#: chunk producers whose result is the residency plane's unit
CHUNK_PRODUCERS = {"pad_chunk", "device_chunk", "device_chunk_on"}


def _full_region_pull(arg) -> str:
    """The CSR region attribute ``arg`` fully materializes, or ''.
    Full = the bare attribute (``view._indices``) or a whole-region
    slice of it (``view._indices[:]``). An element/range subscript
    (``self._indices[eid]``) reads only the rows asked for — O(slice),
    exactly the mmap contract — and is not flagged."""
    if isinstance(arg, ast.Subscript):
        sl = arg.slice
        if not (isinstance(sl, ast.Slice) and sl.lower is None
                and sl.upper is None and sl.step is None):
            return ""
        arg = arg.value
    if isinstance(arg, ast.Attribute) and arg.attr in CSR_REGION_ATTRS:
        return arg.attr
    return ""


def _chunk_valued(arg) -> bool:
    """True when ``arg`` is recognizably a streamed chunk: a
    ``pad_chunk``/``device_chunk`` call, or a name whose terminal
    mentions 'chunk' (the drivers' naming convention)."""
    if isinstance(arg, ast.Call):
        return _terminal(arg.func) in CHUNK_PRODUCERS
    return "chunk" in _terminal(arg).lower()


class _SpillPath(ast.NodeVisitor):
    """The two regression classes the out-of-core plane (ISSUE 20)
    creates room for:

    - ``np.asarray``/``np.array`` over a whole mmap CSR region pulls
      the DISK tier entirely into host memory — the working set is
      back to O(E) and the budget means nothing;
    - a per-chunk ``jax.device_put``/``jnp.asarray`` upload inside a
      loop puts evictable chunk bytes on device OUTSIDE the residency
      manager: HBM the budget model cannot account, spill, or evict at
      a checkpoint boundary (the blessed paths go through
      ``_residency_chunks``/``admit`` or the staged H2D ring).

    Designed windows (the refine re-stream, device-synth placement
    relays) carry ``# sheeplint: spill-ok``."""

    def __init__(self, ctx: RuleContext):
        self.ctx = ctx
        self.loop_depth = 0

    def _loop(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_While = visit_AsyncFor = _loop

    def _def(self, node):
        # a nested function's body does not execute per iteration of
        # the enclosing loop; it gets its own scan at depth 0
        depth, self.loop_depth = self.loop_depth, 0
        self.generic_visit(node)
        self.loop_depth = depth

    visit_FunctionDef = visit_AsyncFunctionDef = visit_Lambda = _def

    def visit_Call(self, node):
        if _is_np_pull(node) and node.args:
            region = _full_region_pull(node.args[0])
            if region:
                self.ctx.add(
                    "spill", "error", node,
                    f"np.{_terminal(node.func)}() over the whole "
                    f"'{region}' mmap region materializes the disk "
                    "tier into host memory — slice the rows you need "
                    "(the view stays O(slice)), or annotate a designed "
                    "window with '# sheeplint: spill-ok'")
        elif self.loop_depth > 0 and node.args:
            term = _terminal(node.func)
            root = _root(node.func)
            uploader = term == "device_put" or (
                term in ("asarray", "array") and root in ("jnp", "jax"))
            if uploader and _chunk_valued(node.args[0]):
                self.ctx.add(
                    "spill", "error", node,
                    f"{root}.{term}() of a chunk inside a loop puts "
                    "evictable bytes on device outside the residency "
                    "manager — HBM the budget cannot account or spill; "
                    "serve chunks through the residency/H2D staging "
                    "path, or annotate a designed window with "
                    "'# sheeplint: spill-ok'")
        self.generic_visit(node)


def check_spill(ctx: RuleContext) -> None:
    _SpillPath(ctx).visit(ctx.tree)


# ---------------------------------------------------------------------------
# lock discipline
# ---------------------------------------------------------------------------

def _self_attr(node, names=None):
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        if names is None or node.attr in names:
            return node.attr
    return None


def _lock_writes(node, lock_attrs, under_lock, out) -> None:
    """Collect (attr, node, under_lock) for self-attribute writes and
    mutating method calls, tracking `with self.<lock>:` nesting."""
    if isinstance(node, ast.With):
        locked = under_lock or any(
            _self_attr(i.context_expr, lock_attrs) for i in node.items)
        for sub in node.body:
            _lock_writes(sub, lock_attrs, locked, out)
        return
    if isinstance(node, (ast.Assign, ast.AugAssign)):
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for t in targets:
            if isinstance(t, ast.Subscript):
                t = t.value
            attr = _self_attr(t)
            if attr:
                out.append((attr, node, under_lock))
    for child in ast.iter_child_nodes(node):
        if isinstance(child, ast.Call) \
                and isinstance(child.func, ast.Attribute) \
                and child.func.attr in LOCK_MUTATING_METHODS:
            attr = _self_attr(child.func.value)
            if attr:
                out.append((attr, child, under_lock))
        _lock_writes(child, lock_attrs, under_lock, out)


def check_locks(ctx: RuleContext) -> None:
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        lock_attrs = set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and _terminal(node.value.func) in ("Lock", "RLock"):
                for t in node.targets:
                    attr = _self_attr(t)
                    if attr:
                        lock_attrs.add(attr)
        if not lock_attrs:
            continue
        writes: list = []
        for meth in cls.body:
            if isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and meth.name != "__init__":
                for st in meth.body:
                    _lock_writes(st, lock_attrs, False, writes)
        protected = {a for a, _, locked in writes if locked} - lock_attrs
        for attr, node, locked in writes:
            if attr in protected and not locked:
                ctx.add(
                    "lock", "error", node,
                    f"self.{attr} is written under "
                    f"{'/'.join('self.' + a for a in sorted(lock_attrs))} "
                    "elsewhere but mutated here without it — a racing "
                    "thread (heartbeat/prefetch worker) can interleave")


# ---------------------------------------------------------------------------

ALL_CHECKS = (check_sync_donate, check_jit_hygiene, check_resources,
              check_locks, check_h2d, check_fold, check_spill)


def check_file(path: str, source: str, tree: ast.Module,
               index: PackageIndex) -> list:
    ctx = RuleContext(path, source, tree, index)
    for chk in ALL_CHECKS:
        chk(ctx)
    ctx.findings.sort(key=lambda f: (f.line, f.rule))
    return ctx.findings
