"""tpulint analyzer — stdlib-``ast`` staging/tracing rules for JAX.

Generic linters see Python; the expensive bugs in this codebase live in
the seam between host Python and staged XLA.  A ``float()`` on a traced
value is a blocking device sync, an ``if`` on a traced array is a
``TracerBoolConversionError`` at best and a silent per-call retrace at
worst, and a missing ``donate_argnums`` doubles the HBM a train step
holds.  Every rule here encodes one of those seams.

The analysis is two-tier, which is what keeps the false-positive rate
workable on a codebase that interleaves host orchestration with jitted
calls (``serving/continuous.py`` is 1.4k lines of exactly that):

1.  **Module index.**  Build lexical scopes, a local call graph, and
    the set of *traced* functions: seeded from ``jax.jit`` / ``pjit``
    decorations and call sites (including ``jax.jit(partial(f, ...))``
    and aliases like ``fn = a if cond else b``), transform/combinator
    arguments (``lax.scan`` bodies, ``jax.vmap`` targets,
    ``custom_vjp`` rules, ``pallas_call`` kernels), and methods of
    ``nn.Module`` subclasses — then closed over intra-module calls and
    lexical nesting.  A param-staticness fixpoint then separates array
    params from config flags: a param bound by ``partial(fn,
    use_sample=...)`` at the jit site, named in ``static_argnames``,
    carrying a literal default, or receiving only static expressions at
    every local call site is *static*, so ``if use_sample:`` is a
    compile-time branch, not a tracer branch.
2.  **Rule pass.**  Walk each function with that context (traced?,
    which names hold device values?, loop depth) and emit findings.

The analyzed code is never imported; everything here is stdlib.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "TZ000": "file could not be parsed",
    "TZ001": "host-device sync inside traced code or a per-iteration host loop",
    "TZ002": "Python `if`/`while` branches on a traced value",
    "TZ003": "`jnp` ops inside a Python loop over a dynamic/shape-dependent range",
    "TZ004": "`jax.jit` constructed per call (inside a loop, under trace, or immediately invoked)",
    "TZ005": "mutable or array-valued default argument on a jitted entry point",
    "TZ006": "host RNG (`np.random`/`random`) inside traced code",
    "TZ007": "`jnp.asarray`/`jnp.array` without explicit dtype in a serving hot path",
    "TZ008": "train-step-shaped jit without `donate_argnums`",
    # TZ1xx: concurrency family — implemented in lockflow.py, listed
    # here so --list-rules/--select/--rules see one catalog.
    "TZ101": "write to a lock-guarded attribute outside its owning lock",
    "TZ102": "blocking call (device sync/sleep/IO) while holding a lock",
    "TZ103": "callback under lock is not provably record-only",
    "TZ104": "inconsistent lock-acquisition order (deadlock cycle)",
    "TZ105": "double-acquire of a non-reentrant Lock",
    "TZ106": "manually acquired lock not released on an early exit path",
    "TZ107": "shared mutable state touched from a threaded entry point "
             "with no lock held",
    "TZ108": "Condition.wait without an enclosing predicate re-check loop",
}

# Files where implicit-dtype conversions (TZ007) matter: the request
# path, where a promotion changes the compiled signature per call.
DEFAULT_HOT_PATHS: Tuple[str, ...] = (
    "serving/",
    "models/lm.py",
    "models/speculative.py",
    "ops/",
    "learn/inference_model.py",
)

_JIT_CALLS = {"jax.jit", "jit", "pjit", "jax.pjit", "nn.jit", "shard_map"}
_PARTIAL_CALLS = {"partial", "functools.partial"}
_DEVICE_GET = {"jax.device_get", "device_get"}
_NP_CONVERT = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "np.ascontiguousarray"}
# TZ007 targets -> index of the positional dtype argument
_JNP_CONVERT = {"jnp.asarray": 1, "jnp.array": 1, "jax.numpy.asarray": 1,
                "jax.numpy.array": 1, "jnp.zeros": 1, "jnp.ones": 1,
                "jax.numpy.zeros": 1, "jax.numpy.ones": 1,
                "jnp.full": 2, "jax.numpy.full": 2,
                "jnp.empty": 1, "jax.numpy.empty": 1}
# Calls whose *result* is a host/static value even on device inputs.
_STATIC_CALLS = {"len", "str", "isinstance", "getattr", "hasattr", "type",
                 "tuple", "sorted", "zip", "enumerate", "range", "dict",
                 "frozenset", "repr", "format",
                 "jnp.ndim", "jnp.shape", "jnp.size", "jnp.result_type",
                 "jnp.promote_types", "jnp.dtype", "jax.eval_shape",
                 "np.dtype", "jnp.issubdtype", "np.issubdtype"}
_DEVICE_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "lax.", "jax.random.",
                    "jax.nn.", "jax.scipy.", "jsp.", "jax.ops.")
_DEVICE_EXACT = {"jax.device_put"}
# Combinators/transforms whose function-valued arguments are traced.
_COMBINATOR_TAILS = {"scan", "while_loop", "fori_loop", "cond", "switch",
                     "associative_scan", "map", "checkpoint", "remat",
                     "vmap", "pmap", "grad", "value_and_grad", "custom_vjp",
                     "custom_jvp", "pallas_call", "defvjp", "defjvp"}
_COMBINATOR_BARE = {"vmap", "pmap", "grad", "value_and_grad", "checkpoint",
                    "remat", "pallas_call", "custom_vjp", "custom_jvp"}
_STATIC_ANNOTATIONS = {"bool", "str", "int"}
_TRAIN_STEP_RE = re.compile(r"(train|update|fit|sgd|optimizer)_?step", re.I)
_SUPPRESS_RE = re.compile(
    r"#\s*tpulint:\s*disable(?P<next>-next-line)?\s*=\s*"
    r"(?P<rules>all|[A-Z]{2}\d{3}(?:\s*,\s*[A-Z]{2}\d{3})*)")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at one source location."""

    rule: str
    path: str
    line: int
    col: int
    message: str
    text: str = ""      # stripped source line — the baseline match key

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"

    def to_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_device_call(dotted: Optional[str]) -> bool:
    if not dotted or dotted in _STATIC_CALLS:
        return False
    return dotted in _DEVICE_EXACT or dotted.startswith(_DEVICE_PREFIXES)


_COMBINATOR_ROOTS = {"jax", "lax", "jnp", "nn", "pl", "flax", "linen"}


def _is_combinator(dotted: Optional[str]) -> bool:
    if not dotted or "tree" in dotted:       # jax.tree.map runs on host
        return False
    tail = dotted.rsplit(".", 1)[-1]
    if tail not in _COMBINATOR_TAILS:
        return False
    if "." not in dotted:
        return tail in _COMBINATOR_BARE
    # require a JAX-ish root so executor.map / pool.map stay host code
    root = dotted.split(".", 1)[0]
    return root in _COMBINATOR_ROOTS or tail in ("defvjp", "defjvp")


def _literal_default(node: Optional[ast.AST]) -> bool:
    """Defaults that hash/compare as compile-time constants."""
    if node is None:
        return False
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp) and isinstance(node.operand, ast.Constant):
        return True
    if isinstance(node, ast.Tuple):
        return all(_literal_default(e) for e in node.elts)
    return False


def _bad_default(node: Optional[ast.AST]) -> bool:
    """Defaults that are mutable or array-valued (TZ005)."""
    if node is None:
        return False
    if isinstance(node, (ast.List, ast.Dict, ast.Set)):
        return True
    if isinstance(node, ast.Call):
        return True        # np.zeros(...), jnp.asarray(...), dict(), ...
    return False


class _Class:
    def __init__(self, name: str, node: ast.ClassDef, scope: "_Scope"):
        self.name = name
        self.node = node
        self.scope = scope
        self.bases: List[str] = [d for d in (_dotted(b) for b in node.bases) if d]
        self.is_module = False      # nn.Module-ish, filled in later


class _Func:
    def __init__(self, node: ast.AST, qualname: str, scope: "_Scope",
                 cls: Optional[_Class]):
        self.node = node
        self.qualname = qualname
        self.name = node.name
        self.scope = scope          # the scope of this function's *body*
        self.cls = cls
        self.traced = False
        self.seed = False           # direct jit/transform boundary
        self.seed_static: Set[str] = set()   # params bound statically at the seed
        self.edges_in: List[Tuple[Optional["_Func"], ast.Call]] = []
        self.edges_out: List["_Func"] = []
        self.device_names: Set[str] = set()

        a = node.args
        pos = list(a.posonlyargs) + list(a.args)
        self.params: List[str] = [p.arg for p in pos if p.arg not in ("self", "cls")]
        self.kwonly: List[str] = [p.arg for p in a.kwonlyargs]
        self.all_params = self.params + self.kwonly
        self.literal_static: Set[str] = set()
        defaults = [None] * (len(pos) - len(a.defaults)) + list(a.defaults)
        for p, d in zip(pos, defaults):
            if _literal_default(d):
                self.literal_static.add(p.arg)
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if _literal_default(d):
                self.literal_static.add(p.arg)
        for p in pos + list(a.kwonlyargs):
            ann = getattr(p, "annotation", None)
            if isinstance(ann, ast.Name) and ann.id in _STATIC_ANNOTATIONS:
                self.literal_static.add(p.arg)
        # optimistically static; the fixpoint demotes (seeds are pinned there)
        self.static: Dict[str, bool] = {p: True for p in self.all_params}
        self.bad_defaults: List[ast.AST] = [d for d in list(a.defaults) +
                                            [k for k in a.kw_defaults if k]
                                            if _bad_default(d)]


class _Scope:
    def __init__(self, kind: str, parent: Optional["_Scope"], qualname: str,
                 func: Optional[_Func] = None, cls: Optional[_Class] = None):
        self.kind = kind            # "module" | "class" | "function"
        self.parent = parent
        self.qualname = qualname
        self.func = func            # the _Func whose body this scope is
        self.cls = cls
        self.funcs: Dict[str, _Func] = {}
        self.classes: Dict[str, _Class] = {}
        self.aliases: Dict[str, Tuple[str, ...]] = {}

    def chain(self) -> List["_Scope"]:
        out, s = [], self
        while s is not None:
            out.append(s)
            s = s.parent
        return out


class _ModuleIndex:
    """Pass 1+2: scopes, seeds, call graph, traced closure, staticness."""

    def __init__(self, tree: ast.Module):
        self.module_scope = _Scope("module", None, "")
        self.funcs: List[_Func] = []
        self._collect(tree.body, self.module_scope, cls=None)
        self._mark_modules()
        self._apply_methods = self._collect_apply_methods(tree)
        self._index(tree.body, self.module_scope)
        self._close_traced()
        self._staticness_fixpoint()
        self._compute_device_names()

    # -- pass 1: scopes / defs / aliases ------------------------------------
    def _collect(self, body: Sequence[ast.stmt], scope: _Scope,
                 cls: Optional[_Class]) -> None:
        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{scope.qualname}.{st.name}" if scope.qualname else st.name
                fn = _Func(st, qual, None, cls if scope.kind == "class" else None)
                child = _Scope("function", scope, qual, func=fn)
                fn.scope = child
                scope.funcs[st.name] = fn
                self.funcs.append(fn)
                self._collect(st.body, child, cls=None)
            elif isinstance(st, ast.ClassDef):
                qual = f"{scope.qualname}.{st.name}" if scope.qualname else st.name
                c = _Class(st.name, st, None)
                child = _Scope("class", scope, qual, cls=c)
                c.scope = child
                scope.classes[st.name] = c
                self._collect(st.body, child, cls=c)
            elif isinstance(st, ast.Assign) and len(st.targets) == 1 and \
                    isinstance(st.targets[0], ast.Name):
                tgt = st.targets[0].id
                if isinstance(st.value, ast.Name):
                    scope.aliases[tgt] = (st.value.id,)
                elif isinstance(st.value, ast.IfExp) and \
                        isinstance(st.value.body, ast.Name) and \
                        isinstance(st.value.orelse, ast.Name):
                    scope.aliases[tgt] = (st.value.body.id, st.value.orelse.id)
            if isinstance(st, (ast.If, ast.For, ast.While, ast.With, ast.Try)):
                for attr in ("body", "orelse", "finalbody"):
                    self._collect(getattr(st, attr, []) or [], scope, cls)
                for h in getattr(st, "handlers", []) or []:
                    self._collect(h.body, scope, cls)

    def _mark_modules(self) -> None:
        classes: List[_Class] = []

        def walk(s: _Scope) -> None:
            classes.extend(s.classes.values())
            for f in s.funcs.values():
                walk(f.scope)
            for c in s.classes.values():
                walk(c.scope)

        walk(self.module_scope)
        by_name = {c.name: c for c in classes}
        changed = True
        while changed:
            changed = False
            for c in classes:
                if c.is_module:
                    continue
                for b in c.bases:
                    tail = b.rsplit(".", 1)[-1]
                    if "Module" in tail or (b in by_name and by_name[b].is_module):
                        c.is_module = True
                        changed = True

    # -- name resolution ----------------------------------------------------
    def _resolve_func(self, name: str, scope: _Scope,
                      _depth: int = 0) -> Optional[_Func]:
        if _depth > 8:
            return None
        for s in scope.chain():
            if s.kind == "class":
                continue            # class bodies are not in method scope
            if name in s.funcs:
                return s.funcs[name]
            if name in s.aliases:
                for tgt in s.aliases[name]:
                    r = self._resolve_func(tgt, s, _depth + 1)
                    if r is not None:
                        return r
                return None
        return None

    def _resolve_method(self, name: str, scope: _Scope) -> Optional[_Func]:
        for s in scope.chain():
            if s.kind == "class" and name in s.funcs:
                return s.funcs[name]
            if s.func is not None and s.func.cls is not None:
                owner = s.func.cls.scope
                if name in owner.funcs:
                    return owner.funcs[name]
        return None

    def _call_targets(self, node: ast.AST, scope: _Scope,
                      ) -> List[Tuple[_Func, Set[str]]]:
        """Functions a jit/transform argument expression refers to, plus
        the param names it binds statically (partial kwargs)."""
        if isinstance(node, ast.Name):
            f = self._resolve_func(node.id, scope)
            return [(f, set())] if f else []
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and node.value.id == "self":
            f = self._resolve_method(node.attr, scope)
            return [(f, set())] if f else []
        if isinstance(node, ast.Call) and _dotted(node.func) in _PARTIAL_CALLS \
                and node.args:
            inner = self._call_targets(node.args[0], scope)
            bound = {kw.arg for kw in node.keywords if kw.arg}
            return [(f, s | bound) for f, s in inner]
        if isinstance(node, ast.IfExp):
            return (self._call_targets(node.body, scope) +
                    self._call_targets(node.orelse, scope))
        return []

    # -- pass 2: seeds + call edges -----------------------------------------
    def _seed(self, fn: Optional[_Func], static: Set[str],
              jit_call: Optional[ast.Call]) -> None:
        if fn is None:
            return
        fn.seed = True
        fn.seed_static |= static
        if jit_call is not None:
            for kw in jit_call.keywords:
                if kw.arg == "static_argnames":
                    for n in ast.walk(kw.value):
                        if isinstance(n, ast.Constant) and isinstance(n.value, str):
                            fn.seed_static.add(n.value)
                elif kw.arg == "static_argnums":
                    for n in ast.walk(kw.value):
                        if isinstance(n, ast.Constant) and isinstance(n.value, int):
                            if 0 <= n.value < len(fn.params):
                                fn.seed_static.add(fn.params[n.value])

    def _module_traced_method(self, fn: _Func, node: ast.AST) -> bool:
        """Which methods of an ``nn.Module`` subclass are traced?  Not
        all of them — wrapper classes (Keras-style nets) hang host
        orchestration (`fit`, `predict`, I/O) off the same class.  The
        trace-shaped ones are ``__call__``/``setup``, ``@nn.compact``
        methods, and anything referenced as an ``apply`` method
        (``model.apply(..., method=Cls.meth)``) anywhere in the module;
        the call-graph closure pulls in their helpers."""
        if fn.name in ("__call__", "setup"):
            return True
        for dec in node.decorator_list:
            d = _dotted(dec)
            if d and d.rsplit(".", 1)[-1] in ("compact", "remat", "jit"):
                return True
        return fn.qualname in self._apply_methods

    def _collect_apply_methods(self, tree: ast.Module) -> Set[str]:
        """Qualnames referenced as ``Cls.meth`` in any ``*.apply(...)``
        call (positionally or via ``method=``)."""
        out: Set[str] = set()
        classes: Dict[str, str] = {}

        def walk_scope(s: _Scope) -> None:
            for name, c in s.classes.items():
                classes.setdefault(name, c.scope.qualname)
                walk_scope(c.scope)
            for f in s.funcs.values():
                walk_scope(f.scope)

        walk_scope(self.module_scope)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if not d or not d.endswith(".apply"):
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                ad = _dotted(arg)
                if ad and "." in ad:
                    cls, meth = ad.rsplit(".", 1)
                    if cls in classes:
                        out.add(f"{classes[cls]}.{meth}")
        return out

    def _index(self, body: Sequence[ast.stmt], scope: _Scope) -> None:
        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = scope.funcs[st.name]
                for dec in st.decorator_list:
                    d = _dotted(dec)
                    if d in _JIT_CALLS or _is_combinator(d):
                        self._seed(fn, set(), None)
                    elif isinstance(dec, ast.Call):
                        dc = _dotted(dec.func)
                        if dc in _JIT_CALLS or _is_combinator(dc):
                            self._seed(fn, set(), dec)
                        elif dc in _PARTIAL_CALLS and dec.args:
                            inner = _dotted(dec.args[0])
                            if inner in _JIT_CALLS or _is_combinator(inner):
                                self._seed(fn, set(), dec)
                if fn.cls is not None and fn.cls.is_module and \
                        self._module_traced_method(fn, st):
                    fn.seed = True
                self._index(st.body, fn.scope)
                continue
            if isinstance(st, ast.ClassDef):
                self._index(st.body, scope.classes[st.name].scope)
                continue
            if isinstance(st, (ast.If, ast.While, ast.For, ast.AsyncFor,
                               ast.With, ast.AsyncWith, ast.Try)):
                # scan only the header expressions here; the nested
                # statement lists recurse so defs land in the right scope
                for child in ast.iter_child_nodes(st):
                    if isinstance(child, ast.expr):
                        self._scan_calls(child, scope)
                for item in getattr(st, "items", []) or []:
                    self._scan_calls(item.context_expr, scope)
                for attr in ("body", "orelse", "finalbody"):
                    sub = getattr(st, attr, None)
                    if isinstance(sub, list):
                        self._index(sub, scope)
                for h in getattr(st, "handlers", []) or []:
                    self._index(h.body, scope)
            else:
                self._scan_calls(st, scope)

    def _scan_calls(self, node: ast.AST, scope: _Scope) -> None:
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            d = _dotted(sub.func)
            if d in _JIT_CALLS and sub.args:
                for fn, static in self._call_targets(sub.args[0], scope):
                    self._seed(fn, static, sub)
            elif _is_combinator(d):
                for arg in list(sub.args) + [k.value for k in sub.keywords]:
                    for fn, static in self._call_targets(arg, scope):
                        self._seed(fn, static, None)
            elif d is not None and scope.func is not None:
                callee = None
                if "." not in d:
                    callee = self._resolve_func(d, scope)
                elif d.startswith("self.") and d.count(".") == 1:
                    callee = self._resolve_method(d.split(".")[1], scope)
                if callee is not None:
                    callee.edges_in.append((scope.func, sub))
                    scope.func.edges_out.append(callee)

    # -- traced closure -----------------------------------------------------
    def _close_traced(self) -> None:
        work = [f for f in self.funcs if f.seed]
        for f in work:
            f.traced = True
        while work:
            f = work.pop()
            nxt = list(f.edges_out)
            nxt.extend(f.scope.funcs.values())      # nested defs trace too
            for g in nxt:
                if not g.traced:
                    g.traced = True
                    work.append(g)

    # -- param staticness ---------------------------------------------------
    def _expr_static(self, expr: ast.AST, scope: _Scope) -> bool:
        if isinstance(expr, ast.Constant):
            return True
        if isinstance(expr, ast.Attribute):
            v = expr.value
            return isinstance(v, ast.Name) and v.id in ("self", "cls")
        if isinstance(expr, ast.Name):
            for s in scope.chain():
                f = s.func
                if f is None:
                    continue
                if expr.id in f.static:
                    return (not f.traced) or f.static[expr.id]
            if self._resolve_func(expr.id, scope) is not None:
                return True
            return False
        if isinstance(expr, ast.UnaryOp):
            return self._expr_static(expr.operand, scope)
        if isinstance(expr, (ast.BoolOp,)):
            return all(self._expr_static(v, scope) for v in expr.values)
        if isinstance(expr, ast.BinOp):
            return self._expr_static(expr.left, scope) and \
                self._expr_static(expr.right, scope)
        if isinstance(expr, ast.Compare):
            return self._expr_static(expr.left, scope) and \
                all(self._expr_static(c, scope) for c in expr.comparators)
        if isinstance(expr, ast.IfExp):
            return all(self._expr_static(e, scope)
                       for e in (expr.test, expr.body, expr.orelse))
        if isinstance(expr, ast.Tuple):
            return all(self._expr_static(e, scope) for e in expr.elts)
        return False

    def _staticness_fixpoint(self) -> None:
        for f in self.funcs:
            if not f.traced:
                continue
            if f.seed:
                for p in f.all_params:
                    f.static[p] = (p in f.seed_static or
                                   p in f.literal_static)
            elif not f.edges_in:
                # combinator bodies / unresolved callees: params are the
                # array boundary unless literally defaulted
                for p in f.all_params:
                    f.static[p] = p in f.literal_static
        changed = True
        rounds = 0
        while changed and rounds < 10:
            changed, rounds = False, rounds + 1
            for f in self.funcs:
                if not f.traced or f.seed or not f.edges_in:
                    continue
                for caller, call in f.edges_in:
                    if caller is None:
                        continue
                    bound: Dict[str, ast.AST] = {}
                    if any(isinstance(a, ast.Starred) for a in call.args):
                        bound = {p: ast.Call(func=ast.Name(id="_", ctx=ast.Load()),
                                             args=[], keywords=[])
                                 for p in f.params}      # unknown -> dynamic
                    else:
                        for p, a in zip(f.params, call.args):
                            bound[p] = a
                        for kw in call.keywords:
                            if kw.arg:
                                bound[kw.arg] = kw.value
                    for p, a in bound.items():
                        if p in f.static and f.static[p] and \
                                p not in f.literal_static and \
                                not self._expr_static(a, caller.scope):
                            f.static[p] = False
                            changed = True

    # -- device-name dataflow ----------------------------------------------
    def _compute_device_names(self) -> None:
        for f in self.funcs:
            self._device_pass(f.scope, f.node.body, f.device_names)
        self.module_device: Set[str] = set()
        # module-level assignments from device calls (rare, but cheap)

    def _device_pass(self, scope: _Scope, body: Sequence[ast.stmt],
                     names: Set[str]) -> None:
        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = st.value
                targets = st.targets if isinstance(st, ast.Assign) else \
                    [st.target]
                if value is None:
                    continue
                dev = expr_is_device(value, scope, self)
                aug = isinstance(st, ast.AugAssign)
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            if dev:
                                names.add(n.id)
                            elif not aug:    # `x += 1` keeps x on device
                                names.discard(n.id)
            elif isinstance(st, ast.For):
                if expr_is_device(st.iter, scope, self):
                    for n in ast.walk(st.target):
                        if isinstance(n, ast.Name):
                            names.add(n.id)
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(st, attr, None)
                if isinstance(sub, list) and sub and isinstance(sub[0], ast.stmt):
                    self._device_pass(scope, sub, names)
            for h in getattr(st, "handlers", []) or []:
                self._device_pass(scope, h.body, names)

    def is_tracked(self, name: str, scope: _Scope) -> bool:
        """Does ``name`` hold a traced/device value in this scope chain?"""
        for s in scope.chain():
            f = s.func
            if f is None:
                continue
            if name in f.device_names:
                return True
            if name in f.static:            # i.e. name is a param of f
                return f.traced and not f.static[name]
        return False


_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "itemsize"}


def expr_is_device(expr: ast.AST, scope: _Scope, index: _ModuleIndex) -> bool:
    """Conservatively: does this expression produce/contain a traced or
    device value?  ``.shape``/``.ndim``/``len()``/``isinstance()`` punch
    through to static, as do identity comparisons (``x is None``)."""
    if isinstance(expr, ast.Constant):
        return False
    if isinstance(expr, ast.Name):
        return index.is_tracked(expr.id, scope)
    if isinstance(expr, ast.Attribute):
        if expr.attr in _STATIC_ATTRS:
            return False
        return expr_is_device(expr.value, scope, index)
    if isinstance(expr, ast.Call):
        d = _dotted(expr.func)
        if d in ("int", "float", "bool", "len") or d in _STATIC_CALLS:
            return False                     # result lives on host
        if d in _DEVICE_GET:
            return False                     # fetches TO host by definition
        if d and (d.startswith("np.") or d.startswith("numpy.")):
            return False                     # numpy results live on host
        if _is_device_call(d):
            return True
        return any(expr_is_device(a, scope, index) for a in expr.args) or \
            any(expr_is_device(k.value, scope, index) for k in expr.keywords)
    if isinstance(expr, ast.Subscript):
        return expr_is_device(expr.value, scope, index)
    if isinstance(expr, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops):
            return False                     # `x is None` is trace-static
        return expr_is_device(expr.left, scope, index) or \
            any(expr_is_device(c, scope, index) for c in expr.comparators)
    if isinstance(expr, (ast.BoolOp,)):
        return any(expr_is_device(v, scope, index) for v in expr.values)
    if isinstance(expr, ast.BinOp):
        return expr_is_device(expr.left, scope, index) or \
            expr_is_device(expr.right, scope, index)
    if isinstance(expr, ast.UnaryOp):
        return expr_is_device(expr.operand, scope, index)
    if isinstance(expr, ast.IfExp):
        return any(expr_is_device(e, scope, index)
                   for e in (expr.test, expr.body, expr.orelse))
    if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
        return any(expr_is_device(e, scope, index) for e in expr.elts)
    if isinstance(expr, ast.Starred):
        return expr_is_device(expr.value, scope, index)
    return False


def _mentions_dynamic(expr: ast.AST, scope: _Scope, index: _ModuleIndex) -> bool:
    """Like expr_is_device but WITHOUT the ``.shape`` shield — a range
    over ``x.shape[0]`` is still a shape-dependent unroll."""
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and index.is_tracked(n.id, scope):
            return True
        if isinstance(n, ast.Call) and _is_device_call(_dotted(n.func)):
            return True
    return False


class _RulePass:
    def __init__(self, index: _ModuleIndex, path: str, lines: List[str],
                 hot: bool, suppressed: Dict[int, Set[str]]):
        self.index = index
        self.path = path
        self.lines = lines
        self.hot = hot
        self.suppressed = suppressed
        self.findings: List[Finding] = []

    def emit(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 1)
        sup = self.suppressed.get(line, set())
        if "all" in sup or rule in sup:
            return
        text = self.lines[line - 1].strip() if 0 < line <= len(self.lines) else ""
        self.findings.append(Finding(rule, self.path, line,
                                     getattr(node, "col_offset", 0) + 1,
                                     message, text))

    # -- entry --------------------------------------------------------------
    def run(self, tree: ast.Module) -> List[Finding]:
        self._stmts(tree.body, self.index.module_scope, traced=False, loop=0)
        for f in self.index.funcs:
            if f.seed and f.bad_defaults:
                for d in f.bad_defaults:
                    self.emit("TZ005", d,
                              f"mutable/array-valued default on jitted "
                              f"`{f.name}`: evaluated once at def time, "
                              f"hashed (or aliased) across every trace; "
                              f"use None and build it inside, or a tuple")
            self._stmts(f.node.body, f.scope, traced=f.traced, loop=0)
        self.findings.sort(key=lambda x: (x.path, x.line, x.rule))
        return self.findings

    # -- statement walk -----------------------------------------------------
    def _stmts(self, body: Sequence[ast.stmt], scope: _Scope, traced: bool,
               loop: int) -> None:
        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # body is visited as its own function; decorators/defaults
                # evaluate in THIS scope
                for dec in st.decorator_list:
                    self._exprs(dec, scope, traced, loop)
                continue
            if isinstance(st, ast.ClassDef):
                continue            # methods visited as their own functions
            if isinstance(st, ast.If):
                self._guard(st.test, scope, traced, kind="if")
                self._exprs(st.test, scope, traced, loop)
                self._stmts(st.body, scope, traced, loop)
                self._stmts(st.orelse, scope, traced, loop)
            elif isinstance(st, ast.While):
                self._guard(st.test, scope, traced, kind="while")
                self._exprs(st.test, scope, traced, loop)
                self._stmts(st.body, scope, traced, loop + 1)
                self._stmts(st.orelse, scope, traced, loop + 1)
            elif isinstance(st, ast.For):
                if traced:
                    self._unroll(st, scope)
                self._exprs(st.iter, scope, traced, loop)
                self._stmts(st.body, scope, traced, loop + 1)
                self._stmts(st.orelse, scope, traced, loop + 1)
            elif isinstance(st, (ast.With, ast.AsyncWith)):
                for item in st.items:
                    self._exprs(item.context_expr, scope, traced, loop)
                self._stmts(st.body, scope, traced, loop)
            elif isinstance(st, ast.Try):
                self._stmts(st.body, scope, traced, loop)
                for h in st.handlers:
                    self._stmts(h.body, scope, traced, loop)
                self._stmts(st.orelse, scope, traced, loop)
                self._stmts(st.finalbody, scope, traced, loop)
            else:
                for child in ast.iter_child_nodes(st):
                    if isinstance(child, ast.expr):
                        self._exprs(child, scope, traced, loop)

    # -- TZ002 --------------------------------------------------------------
    def _guard(self, test: ast.expr, scope: _Scope, traced: bool,
               kind: str) -> None:
        if traced and expr_is_device(test, scope, self.index):
            self.emit("TZ002", test,
                      f"`{kind}` on a traced value stages only one branch "
                      f"(or raises TracerBoolConversionError); use "
                      f"jnp.where/lax.cond, or bind the flag statically "
                      f"(partial kwarg / static_argnames)")

    # -- TZ003 --------------------------------------------------------------
    def _unroll(self, st: ast.For, scope: _Scope) -> None:
        it = st.iter
        if isinstance(it, ast.Call) and _dotted(it.func) == "enumerate" \
                and it.args:
            it = it.args[0]
        if not (isinstance(it, ast.Call) and _dotted(it.func) == "range"):
            return
        if not any(_mentions_dynamic(a, scope, self.index) for a in it.args):
            return
        body_has_device = any(
            isinstance(n, ast.Call) and _is_device_call(_dotted(n.func))
            for s in st.body for n in ast.walk(s))
        if body_has_device:
            self.emit("TZ003", st,
                      "Python loop over a dynamic/shape-dependent range "
                      "unrolls one op-copy per iteration into the XLA "
                      "graph and retraces per length; use lax.scan/"
                      "fori_loop or a static bound")

    # -- expression-level rules (TZ001/TZ004/TZ006/TZ007) -------------------
    def _exprs(self, expr: ast.expr, scope: _Scope, traced: bool,
               loop: int) -> None:
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            self._sync(node, d, scope, traced, loop)
            self._jit_site(node, d, scope, traced, loop)
            if traced and d and (d.startswith("np.random.") or
                                 d.startswith("numpy.random.") or
                                 d.startswith("random.")):
                self.emit("TZ006", node,
                          f"`{d}` inside traced code runs once at trace "
                          f"time and folds to a constant — every call "
                          f"replays the same 'random' draw; thread a "
                          f"jax.random key instead")
            if self.hot and d in _JNP_CONVERT:
                explicit = len(node.args) > _JNP_CONVERT[d] or \
                    any(k.arg == "dtype" for k in node.keywords)
                if not explicit:
                    self.emit("TZ007", node,
                              f"`{d}` without an explicit dtype in a "
                              f"serving hot path: weak-type promotion "
                              f"(or a stray float64) changes the "
                              f"compiled signature and retraces; pass "
                              f"dtype=")

    def _sync(self, node: ast.Call, d: Optional[str], scope: _Scope,
              traced: bool, loop: int) -> None:
        hard = None
        if d and d.endswith(".item") and not node.args:
            hard = ".item()"
        elif d in _DEVICE_GET:
            hard = "jax.device_get"
        elif d == "jax.block_until_ready" or (d and
                                              d.endswith(".block_until_ready")):
            hard = "block_until_ready"
        if hard is not None:
            if traced:
                self.emit("TZ001", node,
                          f"{hard} inside traced code forces a host sync "
                          f"mid-graph (or fails under jit); return the "
                          f"value and fetch on the host")
            elif loop > 0:
                self.emit("TZ001", node,
                          f"{hard} inside a host loop syncs every "
                          f"iteration; batch the fetch once outside the "
                          f"loop (one device_get of the whole pytree)")
            return
        wrap = None
        if d in ("int", "float", "bool") and len(node.args) == 1:
            wrap = d
        elif d in _NP_CONVERT and node.args:
            wrap = d
        if wrap is None:
            return
        arg = node.args[0]
        direct = any(isinstance(n, ast.Call) and _is_device_call(_dotted(n.func))
                     for n in ast.walk(arg))
        if traced:
            if direct or expr_is_device(arg, scope, self.index):
                self.emit("TZ001", node,
                          f"{wrap}() on a traced value inside traced code "
                          f"is a concretization error under jit and a "
                          f"blocking sync outside it; keep it on device")
        else:
            if direct:
                self.emit("TZ001", node,
                          f"{wrap}() wrapping a device computation syncs "
                          f"per call and launches a tiny kernel; compute "
                          f"on device in the jitted program, or fetch a "
                          f"batch once with np.asarray and pick on host")
            elif loop > 0 and expr_is_device(arg, scope, self.index):
                self.emit("TZ001", node,
                          f"{wrap}() on a device value inside a host loop "
                          f"syncs every iteration; hoist one batched "
                          f"fetch out of the loop")

    def _jit_site(self, node: ast.Call, d: Optional[str], scope: _Scope,
                  traced: bool, loop: int) -> None:
        # immediately-invoked jit: jax.jit(f, ...)(args)
        if isinstance(node.func, ast.Call) and \
                _dotted(node.func.func) in _JIT_CALLS:
            self.emit("TZ004", node,
                      "jax.jit(...)(...) compiles and throws the cache "
                      "away — every call retraces; bind the jitted "
                      "callable once and reuse it")
        if d not in _JIT_CALLS:
            return
        if loop > 0:
            self.emit("TZ004", node,
                      "jax.jit constructed inside a loop makes a fresh "
                      "compile cache per iteration; hoist it out (or "
                      "memoize like a step-cache dict)")
        elif traced:
            self.emit("TZ004", node,
                      "jax.jit under trace re-enters staging per call; "
                      "construct jits at init/module scope")
        # TZ008: train-step-shaped target without donation
        if node.args:
            names: List[str] = []
            tgt = node.args[0]
            if isinstance(tgt, ast.Call) and \
                    _dotted(tgt.func) in _PARTIAL_CALLS and tgt.args:
                tgt = tgt.args[0]
            dt = _dotted(tgt)
            if dt:
                names.append(dt.rsplit(".", 1)[-1])
            donated = any(k.arg in ("donate_argnums", "donate_argnames")
                          for k in node.keywords)
            if names and _TRAIN_STEP_RE.search(names[0]) and not donated:
                self.emit("TZ008", node,
                          f"jit of `{names[0]}` without donate_argnums: "
                          f"the old params/opt-state stay live while the "
                          f"update computes, doubling peak HBM; donate "
                          f"the state argument")


def _suppressions(lines: List[str]) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, raw in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(raw)
        if not m:
            continue
        rules = {"all"} if m.group("rules") == "all" else \
            {r.strip() for r in m.group("rules").split(",")}
        target = i + 1 if m.group("next") else i
        out.setdefault(target, set()).update(rules)
    return out


def analyze_source(src: str, path: str,
                   hot_paths: Sequence[str] = DEFAULT_HOT_PATHS,
                   concurrency: bool = True) -> List[Finding]:
    """Analyze one module's source. ``path`` is used for reporting and
    hot-path matching (posix-normalized substring match).  The
    concurrency pass (TZ1xx, lockflow.py) runs by default; pass
    ``concurrency=False`` (CLI ``--no-concurrency``) for staging rules
    only."""
    posix = path.replace(os.sep, "/")
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding("TZ000", path, e.lineno or 1, (e.offset or 0) + 1,
                        f"could not parse: {e.msg}", "")]
    lines = src.splitlines()
    index = _ModuleIndex(tree)
    hot = any(pat in posix for pat in hot_paths)
    sup = _suppressions(lines)
    findings = _RulePass(index, path, lines, hot, sup).run(tree)
    if concurrency:
        # import here: lockflow imports Finding/_dotted from this module
        from analytics_zoo_tpu.lint.lockflow import run_lockflow
        findings.extend(run_lockflow(tree, path, lines, sup))
        findings.sort(key=lambda x: (x.path, x.line, x.rule))
    return findings


def analyze_file(path: str, hot_paths: Sequence[str] = DEFAULT_HOT_PATHS,
                 rel_to: Optional[str] = None,
                 concurrency: bool = True) -> List[Finding]:
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    rep = path
    if rel_to:
        try:
            rep = os.path.relpath(path, rel_to)
        except ValueError:
            rep = path
    return analyze_source(src, rep.replace(os.sep, "/"), hot_paths,
                          concurrency=concurrency)


def iter_py_files(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if not d.startswith(".") and
                                 d != "__pycache__")
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.join(root, f))
        elif p.endswith(".py"):
            out.append(p)
    return out


def analyze_paths(paths: Iterable[str],
                  hot_paths: Sequence[str] = DEFAULT_HOT_PATHS,
                  rel_to: Optional[str] = None,
                  concurrency: bool = True) -> List[Finding]:
    """Analyze files/directories; directory walks skip hidden dirs and
    ``__pycache__``.  Paths are reported relative to ``rel_to`` (default
    cwd) so baselines are stable across checkouts."""
    if rel_to is None:
        rel_to = os.getcwd()
    findings: List[Finding] = []
    for f in iter_py_files(paths):
        findings.extend(analyze_file(f, hot_paths, rel_to,
                                     concurrency=concurrency))
    findings.sort(key=lambda x: (x.path, x.line, x.rule))
    return findings
