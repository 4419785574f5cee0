"""Pass 1 — trace-safety lint.

Flags host/trace confusion inside functions reachable from a
``jax.jit`` root in ``serve/engine.py``, ``models/`` or ``kernels/``:

* **TRACE-BRANCH** — Python-level control flow (``if``/``while``/
  ``assert``/ternary/comprehension guard) whose condition is a traced
  value.  Inside jit these raise ``TracerBoolConversionError`` at best
  and silently bake a trace-time constant at worst.
* **TRACE-COERCE** — host coercions of traced values: ``bool()``/
  ``int()``/``float()``/``range()``/``.item()``/``.tolist()``,
  ``not``/``and``/``or`` on tracers, ``math.*`` on tracers.
* **TRACE-HOSTCALL** — host callbacks on traced values (``np.*`` on a
  tracer concretizes; ``time.*`` runs once at trace time; ``print`` of
  a tracer is almost always a stale-debug bug — ``jax.debug.print`` is
  the sanctioned form and is whitelisted).

The analysis is a cross-module, per-parameter taint propagation to a
fixpoint: jit roots are discovered syntactically (``jax.jit(f)``,
``jax.jit(partial(f, static...))`` — partial's bound positionals are
compile-time constants, matching the repo convention — lambdas, and
``static_argnums``/``static_argnames``), the call graph follows
import aliases and ``self.`` method calls, and function values passed
to jax/pallas combinators (``scan``/``cond``/``pallas_call``/
``shard_map``/``pl.when``/…) are analyzed with all parameters traced.

Statically-derived values stay untainted: ``.shape``/``.ndim``/
``.dtype`` reads, packed-container static aux attributes, ``is None``
tests, ``in`` on static containers, and ``len()`` (legal on tracers —
returns a static dim).
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .common import Corpus, Finding, Module, dotted_name, REPO_ROOT
from .rules import TRACE_BRANCH, TRACE_COERCE, TRACE_HOSTCALL

# Directories whose jax.jit calls seed the reachability analysis.
ROOT_DIRS = ("src/repro/serve", "src/repro/models", "src/repro/kernels")

# Attribute reads that yield STATIC (host) values even on tracers /
# packed containers: array metadata + the containers' static aux fields.
STATIC_ATTRS = {
    "shape", "ndim", "dtype", "size", "itemsize",
    "block", "shards", "shard_kind", "act", "nnz", "nv", "k_max",
    "d_model", "d_ff", "block_f",
}

# jax/pallas combinators whose function-valued arguments run traced.
COMBINATOR_SUFFIXES = (
    "scan", "while_loop", "fori_loop", "cond", "switch", "vmap",
    "pmap", "map", "tree_map", "checkpoint", "remat", "pallas_call",
    "shard_map", "custom_vjp", "custom_jvp", "grad", "value_and_grad",
)

HOST_TIME_MODULES = ("time", "datetime")
COERCING_BUILTINS = {"bool", "int", "float", "complex", "range"}
TRACER_METHOD_COERCIONS = {"item", "tolist", "__bool__", "__int__",
                           "__float__"}


class FuncInfo:
    def __init__(self, module: Module, node: ast.AST,
                 cls: Optional[str] = None):
        self.module = module
        self.node = node
        self.cls = cls

    @property
    def label(self) -> str:
        n = getattr(self.node, "name", "<lambda>")
        return "%s%s" % (("%s." % self.cls) if self.cls else "", n)

    def params(self) -> List[str]:
        a = self.node.args
        names = [p.arg for p in getattr(a, "posonlyargs", [])]
        names += [p.arg for p in a.args]
        if self.is_method and names and names[0] == "self":
            pass  # kept; callers skip position 0
        return names

    @property
    def is_method(self) -> bool:
        return self.cls is not None and not self.is_static

    @property
    def is_static(self) -> bool:
        for d in getattr(self.node, "decorator_list", []):
            if isinstance(d, ast.Name) and d.id in ("staticmethod",
                                                    "classmethod"):
                return True
        return False


class _Analyzer:
    def __init__(self, corpus: Corpus):
        self.corpus = corpus
        self.findings: Dict[Tuple, Finding] = {}
        # id(node) -> (FuncInfo, set of tainted param names)
        self.state: Dict[int, Tuple[FuncInfo, Set[str]]] = {}
        self.queue: List[int] = []
        # return-taint memo: id(node) -> does the function return a
        # traced value even with every parameter tainted?
        self._ret_taint: Dict[int, bool] = {}
        self._ret_probing: Set[int] = set()
        self.probing = 0                # >0: suppress finding emission

    # -- worklist -----------------------------------------------------------

    def add_root(self, fi: FuncInfo, tainted: Set[str]) -> None:
        if self.probing:
            return                      # probes must not seed reachability
        key = id(fi.node)
        if key in self.state:
            prev = self.state[key][1]
            if tainted <= prev:
                return
            self.state[key] = (fi, prev | tainted)
        else:
            self.state[key] = (fi, set(tainted))
        if key not in self.queue:
            self.queue.append(key)

    def solve(self) -> List[Finding]:
        steps = 0
        while self.queue and steps < 10000:
            steps += 1
            key = self.queue.pop()
            fi, tainted = self.state[key]
            self._analyze(fi, set(tainted))
        return sorted(self.findings.values(),
                      key=lambda f: (f.path, f.line, f.rule))

    def emit(self, rule: str, mod: Module, line: int, msg: str) -> None:
        if self.probing:
            return
        f = Finding(rule, mod.rel, line, msg)
        self.findings[(f.rule, f.path, f.line, f.message)] = f

    def returns_tainted(self, fi: FuncInfo) -> bool:
        """Does ``fi`` return a traced value when all params are traced?
        Helper predicates over static config/dict structure return
        untainted results; call sites then stay branchable."""
        key = id(fi.node)
        if key in self._ret_taint:
            return self._ret_taint[key]
        if key in self._ret_probing:
            return True                 # recursion: conservative
        self._ret_probing.add(key)
        self.probing += 1
        try:
            params = fi.params()
            if fi.is_method and params and params[0] == "self":
                params = params[1:]
            walker = _BodyWalker(self, fi, set(params))
            if isinstance(fi.node, ast.Lambda):
                result = walker.expr(fi.node.body)
            else:
                walker.run(fi.node.body)
                result = walker.ret_tainted
        finally:
            self.probing -= 1
            self._ret_probing.discard(key)
        self._ret_taint[key] = result
        return result

    # -- function body analysis --------------------------------------------

    def _analyze(self, fi: FuncInfo, tainted_params: Set[str]) -> None:
        env = set(tainted_params)
        body = fi.node.body if not isinstance(fi.node, ast.Lambda) \
            else [ast.Expr(fi.node.body)]
        _BodyWalker(self, fi, env).run(body)

    # -- call resolution ----------------------------------------------------

    def resolve_call(self, fi: FuncInfo,
                     func: ast.AST) -> Optional[FuncInfo]:
        mod = fi.module
        if isinstance(func, ast.Name):
            r = self.corpus.resolve_function(mod, func.id)
            if r is not None:
                return FuncInfo(r[0], r[1])
            return None
        if isinstance(func, ast.Attribute):
            base = func.value
            if isinstance(base, ast.Name):
                if base.id == "self" and fi.cls:
                    methods = mod.classes.get(fi.cls, {})
                    if func.attr in methods:
                        return FuncInfo(mod, methods[func.attr], fi.cls)
                    return None
                r = self.corpus.resolve_attr_function(
                    mod, base.id, func.attr)
                if r is not None:
                    return FuncInfo(r[0], r[1])
        return None

    def propagate(self, callee: FuncInfo, pos_taints: List[bool],
                  kw_taints: Dict[str, bool],
                  skip_self: bool) -> None:
        params = callee.params()
        if skip_self and params and params[0] == "self":
            params = params[1:]
        tainted: Set[str] = set()
        for i, t in enumerate(pos_taints):
            if t and i < len(params):
                tainted.add(params[i])
        for name, t in kw_taints.items():
            if t and name in params:
                tainted.add(name)
        self.add_root(callee, tainted)

    def mark_all_tainted(self, callee: FuncInfo) -> None:
        params = callee.params()
        if callee.is_method and params and params[0] == "self":
            params = params[1:]
        self.add_root(callee, set(params))


class _BodyWalker:
    """Single-function abstract interpreter over taint."""

    def __init__(self, an: _Analyzer, fi: FuncInfo, env: Set[str]):
        self.an = an
        self.fi = fi
        self.mod = fi.module
        self.env = env
        self.local_funcs: Dict[str, ast.AST] = {}
        self.ret_tainted = False

    # ---- entry ------------------------------------------------------------

    def run(self, body: Sequence[ast.stmt]) -> None:
        for stmt in body:
            self.stmt(stmt)

    # ---- statements -------------------------------------------------------

    def stmt(self, node: ast.stmt) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.local_funcs[node.name] = node
            for dec in node.decorator_list:
                # @pl.when(traced): body runs traced in-place
                name = dotted_name(dec.func) if isinstance(
                    dec, ast.Call) else dotted_name(dec)
                if name and name.split(".")[-1] == "when":
                    self._analyze_nested(node, all_tainted=False)
            return
        if isinstance(node, ast.If):
            if self.expr(node.test):
                self.an.emit(TRACE_BRANCH, self.mod, node.lineno,
                             "%s: `if` on a traced value"
                             % self.fi.label)
            for b in node.body + node.orelse:
                self.stmt(b)
        elif isinstance(node, ast.While):
            if self.expr(node.test):
                self.an.emit(TRACE_BRANCH, self.mod, node.lineno,
                             "%s: `while` on a traced value"
                             % self.fi.label)
            for b in node.body + node.orelse:
                self.stmt(b)
        elif isinstance(node, ast.Assert):
            if self.expr(node.test):
                self.an.emit(TRACE_BRANCH, self.mod, node.lineno,
                             "%s: `assert` on a traced value"
                             % self.fi.label)
        elif isinstance(node, ast.For):
            it_tainted = self.expr(node.iter)
            self._bind_target(node.target, it_tainted, node.iter)
            for b in node.body + node.orelse:
                self.stmt(b)
        elif isinstance(node, ast.Assign):
            t = self.expr(node.value)
            for tgt in node.targets:
                self._bind_target(tgt, t, node.value)
        elif isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._bind_target(node.target, self.expr(node.value),
                                  node.value)
        elif isinstance(node, ast.AugAssign):
            t = self.expr(node.value)
            if isinstance(node.target, ast.Name):
                if t:
                    self.env.add(node.target.id)
                else:
                    self.expr(node.target)
        elif isinstance(node, ast.Return):
            if node.value is not None:
                self.ret_tainted |= self.expr(node.value)
        elif isinstance(node, ast.Expr):
            self.expr(node.value)
        elif isinstance(node, ast.With):
            for item in node.items:
                self.expr(item.context_expr)
            for b in node.body:
                self.stmt(b)
        elif isinstance(node, (ast.Try,)):
            for b in (node.body + node.orelse + node.finalbody):
                self.stmt(b)
            for h in node.handlers:
                for b in h.body:
                    self.stmt(b)
        elif isinstance(node, ast.Raise):
            if node.exc is not None:
                self.expr(node.exc)
        # Pass/Import/Global/Delete/etc: nothing traced

    def _bind_target(self, tgt: ast.AST, tainted: bool,
                     value: ast.AST) -> None:
        if isinstance(tgt, ast.Name):
            if tainted:
                self.env.add(tgt.id)
            else:
                self.env.discard(tgt.id)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            elts = tgt.elts
            # enumerate(x): index is static even when x is traced
            skip_first = (isinstance(value, ast.Call)
                          and isinstance(value.func, ast.Name)
                          and value.func.id == "enumerate")
            # zip(a, b, …) unpacked elementwise: taint per component
            if (isinstance(value, ast.Call)
                    and isinstance(value.func, ast.Name)
                    and value.func.id == "zip"
                    and len(value.args) == len(elts)):
                for e, a in zip(elts, value.args):
                    self._bind_target(e, self.expr(a), a)
                return
            for i, e in enumerate(elts):
                self._bind_target(e, tainted and not (
                    skip_first and i == 0), value)
        elif isinstance(tgt, (ast.Subscript, ast.Attribute, ast.Starred)):
            pass

    # ---- nested functions -------------------------------------------------

    def _analyze_nested(self, node: ast.AST,
                        all_tainted: bool) -> None:
        fi = FuncInfo(self.mod, node, self.fi.cls)
        params = fi.params()
        env = set(self.env)             # closure sees enclosing taint
        if all_tainted:
            env.update(params)
        walker = _BodyWalker(self.an, fi, env)
        walker.local_funcs = dict(self.local_funcs)
        if isinstance(node, ast.Lambda):
            walker.expr(node.body)
        else:
            walker.run(node.body)

    def _maybe_function_value(self, node: ast.AST) -> Optional[object]:
        """A function-valued expression: nested def / lambda / corpus
        function reference."""
        if isinstance(node, ast.Lambda):
            return node
        if isinstance(node, ast.Name):
            if node.id in self.local_funcs:
                return self.local_funcs[node.id]
            r = self.an.corpus.resolve_function(self.mod, node.id)
            if r is not None:
                return FuncInfo(r[0], r[1])
        if isinstance(node, ast.Attribute):
            fi = self.an.resolve_call(self.fi, node)
            if fi is not None:
                return fi
        if isinstance(node, ast.Call):
            # partial(f, ...) / checkpoint(f) passed as the callee
            name = dotted_name(node.func)
            if name and name.split(".")[-1] in (
                    "partial", "checkpoint", "remat"):
                return self._maybe_function_value(
                    node.args[0]) if node.args else None
        return None

    def _mark_function_value_tainted(self, val: object) -> None:
        if isinstance(val, FuncInfo):
            self.an.mark_all_tainted(val)
        elif isinstance(val, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            self._analyze_nested(val, all_tainted=True)

    # ---- expressions (return: tainted?) -----------------------------------

    def expr(self, node: ast.AST) -> bool:
        if node is None or isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.env
        if isinstance(node, ast.Attribute):
            base_t = self.expr(node.value)
            if node.attr in STATIC_ATTRS:
                return False
            return base_t
        if isinstance(node, ast.Subscript):
            self.expr(node.slice)
            return self.expr(node.value)
        if isinstance(node, ast.Call):
            return self.call(node)
        if isinstance(node, ast.BoolOp):
            ts = [self.expr(v) for v in node.values]
            # `a and b` bool-coerces every operand but the last
            for v, t in list(zip(node.values, ts))[:-1]:
                if t:
                    self.an.emit(
                        TRACE_COERCE, self.mod, node.lineno,
                        "%s: and/or bool-coerces a traced value (use "
                        "jnp.logical_and/or or jnp.where)"
                        % self.fi.label)
            return any(ts)
        if isinstance(node, ast.UnaryOp):
            t = self.expr(node.operand)
            if t and isinstance(node.op, ast.Not):
                self.an.emit(TRACE_COERCE, self.mod, node.lineno,
                             "%s: `not` bool-coerces a traced value "
                             "(use jnp.logical_not)" % self.fi.label)
            return t
        if isinstance(node, ast.BinOp):
            return self.expr(node.left) | self.expr(node.right)
        if isinstance(node, ast.Compare):
            ts = [self.expr(node.left)] + [self.expr(c)
                                           for c in node.comparators]
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In,
                                   ast.NotIn)) for op in node.ops):
                return False            # identity/containment: static
            return any(ts)
        if isinstance(node, ast.IfExp):
            if self.expr(node.test):
                self.an.emit(TRACE_BRANCH, self.mod, node.lineno,
                             "%s: ternary on a traced value (use "
                             "jnp.where / lax.cond)" % self.fi.label)
            return self.expr(node.body) | self.expr(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self.expr(v) for v in
                       list(node.keys) + list(node.values)
                       if v is not None)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            t = False
            for gen in node.generators:
                it = self.expr(gen.iter)
                self._bind_target(gen.target, it, gen.iter)
                t |= it
                for cond in gen.ifs:
                    if self.expr(cond):
                        self.an.emit(
                            TRACE_BRANCH, self.mod, node.lineno,
                            "%s: comprehension guard on a traced "
                            "value" % self.fi.label)
            if isinstance(node, ast.DictComp):
                t |= self.expr(node.key) | self.expr(node.value)
            else:
                t |= self.expr(node.elt)
            return t
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        if isinstance(node, ast.JoinedStr):
            for v in node.values:
                if isinstance(v, ast.FormattedValue):
                    self.expr(v.value)
            return False
        if isinstance(node, ast.Lambda):
            return False                # analyzed when invoked/passed
        if isinstance(node, (ast.Slice,)):
            for part in (node.lower, node.upper, node.step):
                if part is not None:
                    self.expr(part)
            return False
        return False

    # ---- calls ------------------------------------------------------------

    def call(self, node: ast.Call) -> bool:
        arg_ts = [self.expr(a) for a in node.args]
        kw_ts = {kw.arg: self.expr(kw.value) for kw in node.keywords
                 if kw.arg is not None}
        for kw in node.keywords:
            if kw.arg is None:
                self.expr(kw.value)
        any_tainted = any(arg_ts) or any(kw_ts.values())
        func = node.func
        name = dotted_name(func) or ""
        short = name.split(".")[-1]

        # builtin coercions -------------------------------------------------
        if isinstance(func, ast.Name):
            if func.id in COERCING_BUILTINS and any_tainted:
                self.an.emit(TRACE_COERCE, self.mod, node.lineno,
                             "%s: %s() concretizes a traced value"
                             % (self.fi.label, func.id))
                return False
            if func.id in ("len", "isinstance", "hasattr", "id",
                           "getattr", "repr", "str", "type", "print"):
                if func.id == "print" and any_tainted:
                    self.an.emit(
                        TRACE_HOSTCALL, self.mod, node.lineno,
                        "%s: print() of a traced value runs at trace "
                        "time only (use jax.debug.print)"
                        % self.fi.label)
                return False
            if func.id in ("min", "max", "sum", "abs", "sorted",
                           "zip", "enumerate", "tuple", "list",
                           "dict", "set", "reversed"):
                return any_tainted

        # method-style coercions on tracers --------------------------------
        if isinstance(func, ast.Attribute):
            base_t = self.expr(func.value)
            if base_t and func.attr in TRACER_METHOD_COERCIONS:
                self.an.emit(TRACE_COERCE, self.mod, node.lineno,
                             "%s: .%s() concretizes a traced value"
                             % (self.fi.label, func.attr))
                return False

        # module classification --------------------------------------------
        root_alias = name.split(".")[0] if name else None
        alias_target = self.mod.import_alias.get(root_alias or "", "")
        is_jax = alias_target.startswith("jax") or root_alias == "jax"
        is_np = alias_target in ("numpy",) or root_alias in ("np",)
        is_time = alias_target in HOST_TIME_MODULES \
            or root_alias in HOST_TIME_MODULES
        is_math = alias_target == "math" or root_alias == "math"

        if is_time:
            self.an.emit(TRACE_HOSTCALL, self.mod, node.lineno,
                         "%s: %s() runs on the host at trace time "
                         "(stale inside jit)" % (self.fi.label, name))
            return False
        if is_math and any_tainted:
            self.an.emit(TRACE_COERCE, self.mod, node.lineno,
                         "%s: math.%s concretizes a traced value "
                         "(use jnp)" % (self.fi.label, short))
            return False
        if is_np and any_tainted:
            self.an.emit(TRACE_HOSTCALL, self.mod, node.lineno,
                         "%s: numpy call %s on a traced value "
                         "concretizes it (use jnp)"
                         % (self.fi.label, name))
            return False

        # jax combinators: function-valued args run traced ------------------
        if (is_jax or short in ("pallas_call", "shard_map", "when")
                or name.startswith("pl.")):
            if short in COMBINATOR_SUFFIXES or short == "when":
                for a in list(node.args) + [kw.value
                                            for kw in node.keywords]:
                    val = self._maybe_function_value(a)
                    if val is not None:
                        self._mark_function_value_tainted(val)
            if name == "jax.eval_shape" or short == "eval_shape":
                return False
            if name.startswith("jax.debug"):
                return False
            return True                 # jnp/jax ops yield tracers

    # shim shard_map (corpus function): body runs traced --------------
        if short == "shard_map":
            for a in list(node.args) + [kw.value
                                        for kw in node.keywords]:
                val = self._maybe_function_value(a)
                if val is not None:
                    self._mark_function_value_tainted(val)
            return True

        # partial over a corpus/local function: propagate bound args -------
        if short == "partial" and node.args:
            val = self._maybe_function_value(node.args[0])
            if isinstance(val, FuncInfo):
                self.an.propagate(val, arg_ts[1:], kw_ts,
                                  skip_self=False)
            elif val is not None:
                self._analyze_nested(val, all_tainted=any_tainted)
            return False

        # local nested function call ---------------------------------------
        if isinstance(func, ast.Name) and func.id in self.local_funcs:
            sub = self.local_funcs[func.id]
            fi = FuncInfo(self.mod, sub, self.fi.cls)
            params = fi.params()
            env = set(self.env)
            for i, t in enumerate(arg_ts):
                if i < len(params):
                    (env.add if t else env.discard)(params[i])
            for k, t in kw_ts.items():
                if t:
                    env.add(k)
            walker = _BodyWalker(self.an, fi, env)
            walker.local_funcs = dict(self.local_funcs)
            if isinstance(sub, ast.Lambda):
                return walker.expr(sub.body)
            walker.run(sub.body)
            return walker.ret_tainted

        # corpus-resolved call: propagate per-parameter taint ---------------
        callee = self.an.resolve_call(self.fi, func)
        if callee is not None:
            skip_self = (isinstance(func, ast.Attribute)
                         and isinstance(func.value, ast.Name)
                         and func.value.id == "self"
                         and callee.is_method)
            self.an.propagate(callee, arg_ts, kw_ts, skip_self)
            return any_tainted and self.an.returns_tainted(callee)
        # unresolvable: conservatively taint-propagating, no flag
        return any_tainted


# ---------------------------------------------------------------------------
# jit-root discovery
# ---------------------------------------------------------------------------

# serve/engine.py's jit of a partial under the wrapped function's name
NAMED_JIT = "named_jit"


def _is_jit(node: ast.Call) -> bool:
    name = dotted_name(node.func)
    return name in ("jax.jit", "jit", NAMED_JIT) or (
        name is not None and name.endswith(".jit")
        and name.startswith("jax"))


class _RootFinder(ast.NodeVisitor):
    """Collect (jit call, enclosing class name) pairs."""

    def __init__(self):
        self.roots: List[Tuple[ast.Call, Optional[str]]] = []
        self._cls: List[str] = []

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._cls.append(node.name)
        self.generic_visit(node)
        self._cls.pop()

    def visit_Call(self, node: ast.Call) -> None:
        if _is_jit(node):
            self.roots.append(
                (node, self._cls[-1] if self._cls else None))
        self.generic_visit(node)


def _static_positions(call: ast.Call) -> Tuple[Set[int], Set[str]]:
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(
                        c.value, int):
                    nums.add(c.value)
        elif kw.arg == "static_argnames":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(
                        c.value, str):
                    names.add(c.value)
    return nums, names


def _resolve_root_target(corpus: Corpus, mod: Module,
                         cls: Optional[str],
                         expr: ast.AST) -> Optional[FuncInfo]:
    if isinstance(expr, ast.Name):
        if cls and expr.id in mod.classes.get(cls, {}):
            return FuncInfo(mod, mod.classes[cls][expr.id], cls)
        r = corpus.resolve_function(mod, expr.id)
        if r is not None:
            return FuncInfo(r[0], r[1])
        return None
    if isinstance(expr, ast.Attribute):
        base = expr.value
        if isinstance(base, ast.Name):
            if base.id == "self" and cls:
                methods = mod.classes.get(cls, {})
                if expr.attr in methods:
                    return FuncInfo(mod, methods[expr.attr], cls)
                return None
            r = corpus.resolve_attr_function(mod, base.id, expr.attr)
            if r is not None:
                return FuncInfo(r[0], r[1])
    return None


def _seed_roots(an: _Analyzer, corpus: Corpus,
                root_dirs: Sequence[str]) -> int:
    n = 0
    for mod in corpus.modules.values():
        if not any(mod.rel.startswith(d) for d in root_dirs):
            continue
        rf = _RootFinder()
        rf.visit(mod.tree)
        for call, cls in rf.roots:
            if not call.args:
                continue
            wrapped = call.args[0]
            nums, names = _static_positions(call)
            n += 1
            if isinstance(wrapped, ast.Lambda):
                fi = FuncInfo(mod, wrapped, cls)
                params = [p.arg for p in wrapped.args.args]
                tainted = {p for i, p in enumerate(params)
                           if i not in nums and p not in names}
                an.add_root(fi, tainted)
                continue
            n_static = 0
            target = wrapped
            if dotted_name(call.func) == NAMED_JIT:
                # named_jit(f, s1, s2) is jax.jit(partial(f, s1, s2))
                n_static = len(call.args) - 1
            elif (isinstance(wrapped, ast.Call)
                    and (dotted_name(wrapped.func) or "").split(".")[-1]
                    == "partial"):
                # jax.jit(partial(f, s1, s2, kw=...)): leading
                # positionals and keywords are compile-time constants
                n_static = len(wrapped.args) - 1
                names |= {kw.arg for kw in wrapped.keywords
                          if kw.arg is not None}
                target = wrapped.args[0] if wrapped.args else None
            if target is None:
                continue
            fi = _resolve_root_target(corpus, mod, cls, target)
            if fi is None:
                continue
            params = fi.params()
            if fi.is_method and params and params[0] == "self":
                params = params[1:]
            tainted = {p for i, p in enumerate(params)
                       if i >= n_static and i not in nums
                       and p not in names}
            an.add_root(fi, tainted)
    return n


def run(root: str = REPO_ROOT,
        subdirs: Sequence[str] = ("src",),
        root_dirs: Sequence[str] = ROOT_DIRS) -> List[Finding]:
    corpus = Corpus(root, subdirs)
    an = _Analyzer(corpus)
    _seed_roots(an, corpus, root_dirs)
    return an.solve()
