"""JAX trace purity for the kernel modules (everything importing jax).

Traced functions are discovered structurally: `@jax.jit` decorations
(including `functools.partial(jax.jit, static_argnames=...)`), and
`jax.jit(fn)` / `jax.jit(functools.partial(fn, **static))` call sites —
the repo's lru_cache-builder idiom. Within a traced function a tiny
forward taint pass marks values derived from traced (non-static)
parameters; taint propagates into same-module helpers called with
tainted arguments, so `_wsum`-style helpers are checked with exactly
the parameters that carry tracers.

Rules:
  jax-traced-branch    Python `if`/`while` on a traced value (concretizes
                       the tracer; jax raises TracerBoolConversionError).
                       `x is None` tests and static attribute reads
                       (.shape/.ndim/.dtype/.size, len()) don't count.
  jax-numpy-in-jit     numpy called on a traced value inside a traced
                       function (np.asarray & friends force a host
                       materialization mid-trace).
  jax-host-sync        float()/int()/bool()/.item()/.tolist() on a traced
                       value inside a traced function.
  jax-nonstatic-jit-cache  lru_cache'd jit-builder whose cache key
                       includes an unhashable-annotated parameter or a
                       mutable default.
  jax-item-in-loop     .item()/.block_until_ready() inside a Python
                       for/while loop in a jax module — a per-element
                       device sync in what should be one batched
                       transfer. (warning)
  unguarded-pallas-dispatch  pl.pallas_call without the repo's two
                       Pallas safety seams: a forwarded `interpret`
                       builder parameter and a module-level
                       _PALLAS_ORACLE parity-test pointer that exists.
  unclassified-device-dispatch  bare/broad `except` around a
                       jit-dispatch or pallas_call site that neither
                       classifies into the ComputeError taxonomy
                       (parallel/guard.py) nor re-raises — untyped
                       swallowing of device faults bypasses the
                       breaker/quarantine/telemetry plane.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from .core import (Finding, Module, Rule, annotation_names, func_params,
                   index_functions, is_cache_decorator, qualname)

_NUMPY_ALIASES = {"np", "numpy"}
# static metadata on tracers: reading these is trace-time constant
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "weak_type",
                 "sharding"}
_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_UNHASHABLE_ANNOT = {"list", "List", "dict", "Dict", "set", "Set",
                     "ndarray", "Array", "ArrayLike", "Sequence",
                     "MutableSequence", "bytearray"}


def _static_argnames(call: ast.Call) -> Set[str]:
    """static_argnames=... from a jax.jit / partial(jax.jit, ...) call."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg != "static_argnames":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            out.add(v.value)
        elif isinstance(v, (ast.Tuple, ast.List)):
            for el in v.elts:
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    out.add(el.value)
    return out


_JIT_NAMES = ("jax.jit", "jit", "jax.pjit", "pjit")
_TRANSFORM_NAMES = _JIT_NAMES + (
    "jax.shard_map", "shard_map", "jax.vmap", "vmap", "jax.pmap", "pmap")


def _is_jax_jit(node: ast.AST) -> bool:
    return qualname(node) in _JIT_NAMES


def _is_jax_transform(node: ast.AST) -> bool:
    """Any jax transform that traces its function argument."""
    return qualname(node) in _TRANSFORM_NAMES


def _partial_of(call: ast.Call) -> Optional[ast.AST]:
    """For functools.partial(X, ...) return X, else None."""
    if qualname(call.func) in ("functools.partial", "partial") and call.args:
        return call.args[0]
    return None


def _index_all_functions(mod: Module) -> Dict[str, List[ast.FunctionDef]]:
    """EVERY function def per bare name, in source order — the repo's
    builder idiom defines many distinct nested `fn`s, and resolution
    must not collapse them onto one."""
    out: Dict[str, List[ast.FunctionDef]] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, []).append(node)
    for defs in out.values():
        defs.sort(key=lambda f: f.lineno)
    return out


def _resolve(name: str, use_line: int,
             by_name: Dict[str, List[ast.FunctionDef]],
             ) -> Optional[ast.FunctionDef]:
    """The def a name at `use_line` refers to: the nearest PRECEDING def
    with that name (Python binding order in the builder idiom), falling
    back to the first def when all follow the use site."""
    defs = by_name.get(name)
    if not defs:
        return None
    best = None
    for fn in defs:
        if fn.lineno <= use_line:
            best = fn
        else:
            break
    return best or defs[0]


def find_traced(mod: Module) -> Dict[int, Tuple[ast.FunctionDef, Set[str]]]:
    """id(funcdef) -> (funcdef, static param names) for every function
    the module hands to jax.jit one way or another."""
    by_name = _index_all_functions(mod)
    traced: Dict[int, Tuple[ast.FunctionDef, Set[str]]] = {}

    def mark(fn: ast.FunctionDef, static: Set[str]):
        prev = traced.get(id(fn))
        if prev is not None:
            static = prev[1] & static  # keep the most conservative view
        traced[id(fn)] = (fn, static)

    for defs in by_name.values():
        for fn in defs:
            for dec in fn.decorator_list:
                if _is_jax_transform(dec):
                    mark(fn, set())
                elif isinstance(dec, ast.Call):
                    if _is_jax_transform(dec.func):
                        mark(fn, _static_argnames(dec))
                    else:
                        inner = _partial_of(dec)
                        if inner is not None and _is_jax_transform(inner):
                            mark(fn, _static_argnames(dec))

    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and _is_jax_transform(node.func)
                and node.args):
            continue
        static = _static_argnames(node)
        target = node.args[0]
        if isinstance(target, ast.Name):
            fn = _resolve(target.id, node.lineno, by_name)
            if fn is not None:
                mark(fn, static)
        elif isinstance(target, ast.Call):
            inner = _partial_of(target)
            if isinstance(inner, ast.Name):
                fn = _resolve(inner.id, node.lineno, by_name)
                if fn is not None:
                    # partial-bound keywords are trace-time constants
                    bound = {kw.arg for kw in target.keywords if kw.arg}
                    mark(fn, static | bound)
    return traced


class _TaintVisitor:
    """One pass over a traced function body: tracks names holding traced
    values, records purity violations, and collects same-module calls
    that receive tainted arguments (for interprocedural propagation)."""

    def __init__(self, mod: Module, fn: ast.FunctionDef, tainted: Set[str],
                 local_funcs: Dict[str, ast.FunctionDef]):
        self.mod = mod
        self.fn = fn
        self.tainted = set(tainted)
        self.local_funcs = local_funcs
        self.violations: List[Tuple[str, ast.AST, str]] = []
        self.calls_out: List[Tuple[str, Set[str]]] = []
        # tainted calls to names NOT defined in this module — resolved
        # cross-module by CrossModuleTaintRule over the ProgramIndex:
        # (dotted name, per-positional taint, per-keyword taint, line)
        self.ext_calls: List[Tuple[str, List[bool], Dict[str, bool],
                                   int]] = []

    # -- taint queries ----------------------------------------------------

    def expr_tainted(self, node: ast.AST) -> bool:
        return any(self._tainted_names(node))

    def _tainted_names(self, node: ast.AST) -> Iterator[str]:
        """Tainted Names reachable in an expression without crossing a
        static boundary (.shape et al, len(), isinstance())."""
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return
        if isinstance(node, ast.Call):
            q = qualname(node.func)
            if q in ("len", "isinstance", "type", "id"):
                return
        if isinstance(node, ast.Name):
            if node.id in self.tainted:
                yield node.id
            return
        for child in ast.iter_child_nodes(node):
            yield from self._tainted_names(child)

    def _test_tainted(self, test: ast.AST) -> bool:
        """Tainted-ness of a branch condition; `x is (not) None` legs are
        trace-time constants and don't count."""
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return False
        if isinstance(test, ast.BoolOp):
            return any(self._test_tainted(v) for v in test.values)
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._test_tainted(test.operand)
        return self.expr_tainted(test)

    # -- walking ----------------------------------------------------------

    def run(self):
        # two passes: loop-carried assignments taint their earlier uses
        for _ in range(2):
            self.violations.clear()
            self.calls_out.clear()
            for stmt in self.fn.body:
                self._stmt(stmt)

    def _assign_target(self, target: ast.AST, taint: bool):
        if isinstance(target, ast.Name):
            if taint:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                self._assign_target(el, taint)
        # attribute/subscript stores don't create new tracked names

    def _stmt(self, stmt: ast.AST):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs trace on their own call sites
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = stmt.value
            if value is not None:
                self._expr(value)
                taint = self.expr_tainted(value)
                targets = (stmt.targets if isinstance(stmt, ast.Assign)
                           else [stmt.target])
                for t in targets:
                    if isinstance(stmt, ast.AugAssign):
                        if taint and isinstance(t, ast.Name):
                            self.tainted.add(t.id)
                    else:
                        self._assign_target(t, taint)
            return
        if isinstance(stmt, (ast.If, ast.While)):
            if self._test_tainted(stmt.test):
                kind = "while" if isinstance(stmt, ast.While) else "if"
                self.violations.append((
                    "jax-traced-branch", stmt,
                    f"Python `{kind}` on a traced value inside jitted "
                    f"{self.fn.name!r} — the tracer cannot be concretized; "
                    "use jnp.where/lax.cond/lax.select, or mark the "
                    "argument static"))
            self._expr(stmt.test)
            for s in [*stmt.body, *stmt.orelse]:
                self._stmt(s)
            return
        if isinstance(stmt, ast.For):
            self._expr(stmt.iter)
            self._assign_target(stmt.target, self.expr_tainted(stmt.iter))
            for s in [*stmt.body, *stmt.orelse]:
                self._stmt(s)
            return
        if isinstance(stmt, (ast.With,)):
            for s in stmt.body:
                self._stmt(s)
            return
        if isinstance(stmt, ast.Try):
            for s in [*stmt.body, *stmt.orelse, *stmt.finalbody]:
                self._stmt(s)
            for h in stmt.handlers:
                for s in h.body:
                    self._stmt(s)
            return
        if isinstance(stmt, ast.Return) and stmt.value is not None:
            self._expr(stmt.value)
            return
        if isinstance(stmt, ast.Expr):
            self._expr(stmt.value)
            return
        # everything else (pass/raise/assert/...): still scan expressions
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                self._expr(child)

    def _expr(self, node: ast.AST):
        for call in [n for n in ast.walk(node) if isinstance(n, ast.Call)]:
            self._call(call)
        for ifexp in [n for n in ast.walk(node) if isinstance(n, ast.IfExp)]:
            if self._test_tainted(ifexp.test):
                self.violations.append((
                    "jax-traced-branch", ifexp,
                    f"conditional expression on a traced value inside "
                    f"jitted {self.fn.name!r} — use jnp.where/lax.select"))

    def _call(self, call: ast.Call):
        q = qualname(call.func)
        args_tainted = [self.expr_tainted(a) for a in call.args]
        kw_tainted = {kw.arg: self.expr_tainted(kw.value)
                      for kw in call.keywords if kw.arg}
        any_tainted = any(args_tainted) or any(kw_tainted.values())

        if q and any_tainted:
            root = q.split(".")[0]
            if root in _NUMPY_ALIASES and "." in q:
                self.violations.append((
                    "jax-numpy-in-jit", call,
                    f"{q}() on a traced value inside jitted "
                    f"{self.fn.name!r} — host numpy forces materialization "
                    "mid-trace; use jnp/lax"))
            elif q in _SYNC_BUILTINS:
                self.violations.append((
                    "jax-host-sync", call,
                    f"{q}() concretizes a traced value inside jitted "
                    f"{self.fn.name!r} (TracerError at trace time); keep "
                    "the value symbolic or mark it static"))
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr in _SYNC_METHODS
                and self.expr_tainted(call.func.value)):
            self.violations.append((
                "jax-host-sync", call,
                f".{call.func.attr}() on a traced value inside jitted "
                f"{self.fn.name!r} forces a device sync mid-trace"))
        # propagate taint into same-module helpers
        if (q and "." not in q and q in self.local_funcs and any_tainted):
            callee = self.local_funcs[q]
            names = [a.arg for a in func_params(callee)]
            hit: Set[str] = set()
            for i, t in enumerate(args_tainted):
                if t and i < len(names):
                    hit.add(names[i])
            for k, t in kw_tainted.items():
                if t and k in names:
                    hit.add(k)
            if hit:
                self.calls_out.append((q, hit))
        elif q and any_tainted and q.split(".")[0] not in _NUMPY_ALIASES \
                and q.split(".")[0] not in ("jnp", "jax", "lax"):
            # candidate CROSS-MODULE propagation: an imported helper
            # called with tracers (resolution happens over the
            # ProgramIndex; unresolvable names simply drop out)
            self.ext_calls.append((q, args_tainted, kw_tainted,
                                   call.lineno))


class JaxPurityRule(Rule):
    """jax-traced-branch / jax-numpy-in-jit / jax-host-sync over every
    traced function (direct and taint-transitive)."""

    id = "jax-purity"  # umbrella; findings carry their specific ids
    severity = "error"
    requires_import = "jax"

    def check(self, mod: Module) -> Iterator[Finding]:
        funcs = index_functions(mod)
        traced = find_traced(mod)
        # worklist of (funcdef, tainted param set), seen keyed by node
        # identity — distinct same-named nested builders analyze apart
        seen: Dict[int, Set[str]] = {}
        work: List[Tuple[ast.FunctionDef, Set[str]]] = []
        for fn, static in traced.values():
            params = {a.arg for a in func_params(fn)}
            work.append((fn, params - static))
        emitted: Set[Tuple[str, int, str]] = set()
        while work:
            fn, tainted = work.pop()
            prev = seen.get(id(fn))
            if prev is not None and tainted <= prev:
                continue
            seen[id(fn)] = (prev or set()) | tainted
            v = _TaintVisitor(mod, fn, tainted, funcs)
            v.run()
            for rule_id, node, msg in v.violations:
                line = getattr(node, "lineno", fn.lineno)
                key = (rule_id, line, msg)
                if key in emitted:
                    continue  # re-analysis with a wider taint set
                emitted.add(key)
                yield Finding(rule_id, mod.relpath, line, msg, self.severity)
            for callee, hit in v.calls_out:
                if funcs[callee] is not fn:
                    work.append((funcs[callee], hit))


class NonStaticJitCacheRule(Rule):
    """jax-nonstatic-jit-cache: lru_cache'd builder returning a jitted
    callable whose cache key includes an unhashable parameter."""

    id = "jax-nonstatic-jit-cache"
    severity = "error"
    requires_import = "jax"

    def check(self, mod: Module) -> Iterator[Finding]:
        for fn in index_functions(mod).values():
            if not any(is_cache_decorator(d) for d in fn.decorator_list):
                continue
            if not any(_is_jax_jit(n) or (isinstance(n, ast.Call)
                                          and _is_jax_jit(n.func))
                       for n in ast.walk(fn)):
                continue
            for arg in func_params(fn):
                bad = annotation_names(arg.annotation) & _UNHASHABLE_ANNOT
                if bad:
                    yield self.finding(
                        mod, fn,
                        f"jit-builder {fn.name!r} is lru_cache'd but "
                        f"parameter {arg.arg!r} is annotated "
                        f"{'|'.join(sorted(bad))} — unhashable cache key "
                        "(TypeError) or object-identity keying; take "
                        "hashable scalars/tuples instead")
            defaults = [*fn.args.defaults, *fn.args.kw_defaults]
            for d in defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    yield self.finding(
                        mod, d,
                        f"jit-builder {fn.name!r} is lru_cache'd with a "
                        "mutable default — shared across every cache entry")


class ItemInLoopRule(Rule):
    """jax-item-in-loop: per-element device syncs in Python loops."""

    id = "jax-item-in-loop"
    severity = "warning"
    requires_import = "jax"

    def check(self, mod: Module) -> Iterator[Finding]:
        for loop in ast.walk(mod.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in ast.walk(loop):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("item", "block_until_ready")):
                    yield self.finding(
                        mod, node,
                        f".{node.func.attr}() inside a Python loop — one "
                        "device sync per element; batch the transfer "
                        "(np.asarray once) outside the loop")


class CrossModuleTaintRule:
    """jax purity ACROSS modules (a ProgramRule — see callgraph.py):
    when a traced function calls a helper IMPORTED from another module
    with tracer-carrying arguments, the callee runs under trace too —
    its Python branches, host numpy, and `.item()` syncs fail exactly
    like same-module ones, but the per-module pass cannot see them.
    This rule resolves every tainted external call over the
    ProgramIndex and re-runs the taint pass inside the callee's own
    module with precisely the parameters that carry tracers. Callees
    that are themselves jitted in their home module are skipped — the
    per-module pass already covers them."""

    id = "jax-purity"
    severity = "error"

    _MAX_HOPS = 3  # cross-module hops a tracer is followed through

    def check_program(self, program) -> Iterator[Finding]:
        emitted: Set[Tuple[str, str, int, str]] = set()
        # ONE worklist spanning modules: (module dotted, fn node,
        # tainted params, provenance, cross-module hops). Taint flows
        # through same-module helpers (calls_out) and keeps going
        # through imported ones (ext_calls) — jitted f -> B.h -> h's
        # local helper g must reach g. Findings are yielded only for
        # nodes reached through >=1 cross-module hop; everything
        # same-module belongs to the per-module JaxPurityRule.
        seen: Dict[int, Set[str]] = {}
        work: List[Tuple[str, ast.AST, Set[str], str, int]] = []
        for dotted, mod in sorted(program.modules.items()):
            if "jax" not in mod.imports:
                continue
            for fn, static in find_traced(mod).values():
                params = {a.arg for a in func_params(fn)}
                work.append((dotted, fn, params - static, "", 0))
        while work:
            dotted, fn, tainted, prov, hops = work.pop()
            mod = program.modules[dotted]
            prev = seen.get(id(fn))
            if prev is not None and tainted <= prev:
                continue
            seen[id(fn)] = (prev or set()) | tainted
            funcs = index_functions(mod)
            v = _TaintVisitor(mod, fn, tainted, funcs)
            v.run()
            if prov:
                for rule_id, node, msg in v.violations:
                    vline = getattr(node, "lineno", fn.lineno)
                    key = (rule_id, mod.relpath, vline, msg)
                    if key in emitted:
                        continue
                    emitted.add(key)
                    yield Finding(rule_id, mod.relpath, vline,
                                  f"{msg} [{prov}]", self.severity)
            for callee, hit in v.calls_out:
                if funcs[callee] is not fn:
                    work.append((dotted, funcs[callee], hit, prov, hops))
            if hops >= self._MAX_HOPS:
                continue
            for q, args_t, kw_t, line in v.ext_calls:
                nxt = self._resolve_ext(program, dotted, q, args_t, kw_t)
                if nxt is None:
                    continue
                callee_dotted, callee_fn, hit = nxt
                new_prov = prov or (
                    "reached under trace via cross-module call from "
                    f"{mod.relpath}:{line} in jitted {fn.name!r}")
                work.append((callee_dotted, callee_fn, hit, new_prov,
                             hops + 1))

    def _resolve_ext(self, program, dotted, q, args_t, kw_t):
        r = program.resolve(dotted, q)
        if not r or r[0] != "func":
            return None
        fi = program.functions[r[1]]
        if fi.module == dotted or fi.module not in program.modules:
            return None
        callee_mod = program.modules[fi.module]
        if id(fi.node) in find_traced(callee_mod):
            return None  # jitted at home: per-module pass covers it
        names = [a.arg for a in func_params(fi.node)]
        if names and names[0] in ("self", "cls"):
            names = names[1:]
            # unbound call through the class (`Helper.compute(h, x)`):
            # the first positional argument IS the receiver — drop it so
            # positional taint lines up with the stripped param list
            head = q.rsplit(".", 1)[0] if "." in q else None
            if head and fi.cls is not None:
                hr = program.resolve(dotted, head)
                if hr and hr[0] == "class":
                    args_t = args_t[1:]
        hit: Set[str] = set()
        for i, t in enumerate(args_t):
            if t and i < len(names):
                hit.add(names[i])
        for k, t in kw_t.items():
            if t and k in names:
                hit.add(k)
        if not hit:
            return None
        return fi.module, fi.node, hit


class MeshSpecRule(Rule):
    """mesh-axis-unbound / shard-spec-arity / unannotated-out-sharding:
    shard_map spec consistency for the mesh kernels.

    * `mesh-axis-unbound` — a psum/pmin/pmax/pmean/all_gather collective
      naming an axis that appears NOWHERE in the module's mesh
      declarations (`Mesh(devs, ("shard", "time"))`) or partition specs
      (`P("shard", None)`, nested tuples included). An unbound axis name
      raises at trace time on the real mesh — but only on the code path
      that dispatches sharded, which a single-device CI run never takes.
    * `shard-spec-arity` — `shard_map(_compat)(fn, ..., in_specs=(...))`
      whose static in_specs tuple arity disagrees with the wrapped local
      function's positional parameter count.
    * `unannotated-out-sharding` — in parallel/compile.py ONLY: an
      out_specs entry carrying a sharded `P("shard", ...)` that is not
      conditioned on the plan IR's edge annotation (an `... if
      <edge>.sharding == SHARDED else ...` binding). The plan compiler's
      out-sharding must mirror the SHARDED/REPLICATED edge the IR
      recorded, or a replicated root is scattered (and a sharded one
      gathered) behind the annotation's back.
    """

    id = "mesh-spec"  # umbrella; findings carry their specific ids
    severity = "error"
    dirs = ("parallel", "ops")
    requires_import = "jax"

    _SHARD_MAP_NAMES = ("shard_map", "jax.shard_map")
    _COLLECTIVES = ("psum", "pmin", "pmax", "pmean", "all_gather",
                    "axis_index", "ppermute")
    _MESH_NAMES = ("Mesh", "jax.sharding.Mesh", "jax.make_mesh")
    _SPEC_NAMES = ("P", "PartitionSpec", "jax.sharding.PartitionSpec")

    @classmethod
    def _spec_axis_names(cls, node: ast.AST) -> Set[str]:
        """String constants inside a P(...)/PartitionSpec(...) call
        (tuple-grouped axes like P(("shard", "time")) included)."""
        out: Set[str] = set()
        for n in ast.walk(node):
            if isinstance(n, ast.Call) and qualname(n.func) in cls._SPEC_NAMES:
                for a in ast.walk(ast.Tuple(elts=list(n.args), ctx=ast.Load())):
                    if isinstance(a, ast.Constant) and isinstance(a.value, str):
                        out.add(a.value)
        return out

    @classmethod
    def _axis_vocabulary(cls, mod: Module) -> Set[str]:
        """Axis names DECLARED anywhere in the module: mesh axis tuples
        and partition-spec literals."""
        out: Set[str] = set()
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            q = qualname(node.func)
            if q in cls._MESH_NAMES:
                cands = list(node.args[1:]) + [kw.value for kw in node.keywords
                                               if kw.arg == "axis_names"]
                for c in cands:
                    for a in ast.walk(c):
                        if isinstance(a, ast.Constant) and \
                                isinstance(a.value, str):
                            out.add(a.value)
        out |= cls._spec_axis_names(mod.tree)
        return out

    @staticmethod
    def _local_bindings(fn: ast.AST) -> Dict[str, ast.AST]:
        """name -> value for names assigned exactly once in `fn`."""
        out: Dict[str, ast.AST] = {}
        dup: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                if name in out:
                    dup.add(name)
                out[name] = node.value
            elif isinstance(node, ast.AugAssign) and \
                    isinstance(node.target, ast.Name):
                dup.add(node.target.id)
        for name in dup:
            out.pop(name, None)
        return out

    def _deref_binding(self, node: ast.AST, bindings: Dict[str, ast.AST],
                       depth: int = 2) -> ast.AST:
        while depth > 0 and isinstance(node, ast.Name) and \
                node.id in bindings:
            node = bindings[node.id]
            depth -= 1
        return node

    def check(self, mod: Module) -> Iterator[Finding]:
        vocab = self._axis_vocabulary(mod)
        by_name = _index_all_functions(mod)
        in_compile = bool(mod.scope_parts) and \
            mod.scope_parts[-1] == "compile.py"

        # collective axis names must exist on some declared mesh/spec
        if vocab:
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call) and
                        isinstance(node.func, ast.Attribute) and
                        node.func.attr in self._COLLECTIVES):
                    continue
                axis = None
                if len(node.args) > 1:
                    axis = node.args[1]
                elif node.args and isinstance(node.args[0], ast.Constant):
                    axis = node.args[0]  # axis_index("shard")
                for kw in node.keywords:
                    if kw.arg == "axis_name":
                        axis = kw.value
                if not (isinstance(axis, ast.Constant) and
                        isinstance(axis.value, str)):
                    continue
                if axis.value not in vocab:
                    yield Finding(
                        "mesh-axis-unbound", mod.relpath, node.lineno,
                        f"`{node.func.attr}` over axis "
                        f"{axis.value!r} which is bound by NO mesh or "
                        f"partition spec in this module (declared axes: "
                        f"{sorted(vocab)}) — this raises at trace time "
                        "on the sharded dispatch path only; name an "
                        "axis the bound mesh carries", self.severity)

        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and qualname(node.func) in self._SHARD_MAP_NAMES
                    and node.args):
                continue
            enclosing = mod.enclosing_function(node)
            bindings = self._local_bindings(enclosing) if enclosing else {}
            in_specs = None
            out_specs = None
            for kw in node.keywords:
                if kw.arg == "in_specs":
                    in_specs = self._deref_binding(kw.value, bindings)
                elif kw.arg == "out_specs":
                    # resolve a name-bound tuple so its ELEMENTS (which
                    # keep their IfExp bindings) are what get checked
                    out_specs = self._deref_binding(kw.value, bindings)
            # arity: static in_specs tuple vs the wrapped local def
            target = node.args[0]
            fn_def = None
            if isinstance(target, ast.Name):
                fn_def = _resolve(target.id, node.lineno, by_name)
            if fn_def is not None and isinstance(in_specs, ast.Tuple) \
                    and fn_def.args.vararg is None:
                n_params = len(fn_def.args.posonlyargs) + \
                    len(fn_def.args.args)
                n_defaults = len(fn_def.args.defaults)
                n_specs = len(in_specs.elts)
                if n_specs > n_params or n_specs < n_params - n_defaults:
                    yield Finding(
                        "shard-spec-arity", mod.relpath, node.lineno,
                        f"in_specs carries {n_specs} spec(s) "
                        f"but {fn_def.name!r} takes {n_params} positional "
                        "argument(s) — shard_map raises a tree mismatch "
                        "at trace time on the sharded path", self.severity)
            # compile.py: out-sharding must follow the edge annotation
            if in_compile and out_specs is not None:
                elems = (list(out_specs.elts)
                         if isinstance(out_specs, ast.Tuple) else [out_specs])
                for el in elems:
                    resolved = self._deref_binding(el, bindings)
                    if not self._spec_axis_names(resolved):
                        continue  # replicated P() — nothing to annotate
                    if self._edge_conditioned(el, resolved):
                        continue
                    at = el if hasattr(el, "lineno") else node
                    yield Finding(
                        "unannotated-out-sharding", mod.relpath,
                        getattr(at, "lineno", node.lineno),
                        "sharded out_specs entry is not derived from the "
                        "plan IR's edge annotation — bind it as "
                        "`P(\"shard\", ...) if <edge>.sharding == SHARDED "
                        "else P()` so the program's out-sharding mirrors "
                        "the SHARDED/REPLICATED edge the plan recorded",
                        self.severity)

    @staticmethod
    def _edge_conditioned(orig: ast.AST, resolved: ast.AST) -> bool:
        """The spec binding is an IfExp whose test reads an edge's
        `.sharding` annotation."""
        for cand in (orig, resolved):
            if isinstance(cand, ast.IfExp):
                for n in ast.walk(cand.test):
                    if isinstance(n, ast.Attribute) and \
                            n.attr == "sharding":
                        return True
        return False


class UnguardedPallasDispatchRule(Rule):
    """unguarded-pallas-dispatch: every `pl.pallas_call` site must keep
    the repo's two Pallas safety seams intact.

    1. The enclosing builder must take an `interpret` parameter and
       forward it into the call (`interpret=interpret`). A hard-coded
       `interpret=False` breaks every non-TPU environment (the CPU test
       platform); a hard-coded `True` means real hardware never
       gets a compiled kernel; a missing kwarg silently defaults to
       compiled-only. The parameter seam is what lets the dispatch gate
       (`M3_TPU_PALLAS`) pick per-backend behavior from OUTSIDE the
       lru_cached builder.
    2. The module must declare `_PALLAS_ORACLE = "<path>"` naming the
       test file that asserts interpret-vs-XLA parity, and the path must
       exist. Pallas kernels ship only with a standing bit-identity
       oracle — pallas_codec.py rides this contract, and the constant
       keeps the pointer from rotting silently when tests move.
    """

    id = "unguarded-pallas-dispatch"
    severity = "error"
    requires_import = "jax"

    _PALLAS_CALL = ("pl.pallas_call", "pallas.pallas_call",
                    "jax.experimental.pallas.pallas_call")

    @staticmethod
    def _oracle_decl(mod: Module) -> Optional[str]:
        """Module-level `_PALLAS_ORACLE = "<str literal>"`, or None."""
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    node.targets[0].id == "_PALLAS_ORACLE" and \
                    isinstance(node.value, ast.Constant) and \
                    isinstance(node.value.value, str):
                return node.value.value
        return None

    @staticmethod
    def _repo_root(mod: Module) -> str:
        """Path prefix before the m3_tpu package dir (cwd fallback —
        the analyzer runs from the repo root)."""
        import os

        norm = mod.path.replace(os.sep, "/")
        idx = norm.rfind("/m3_tpu/")
        return mod.path[:idx] if idx > 0 else "."

    def _enclosing_fn(self, mod: Module,
                      node: ast.AST) -> Optional[ast.FunctionDef]:
        cur = mod.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = mod.parents.get(cur)
        return None

    def check(self, mod: Module) -> Iterator[Finding]:
        import os

        sites = [n for n in ast.walk(mod.tree)
                 if isinstance(n, ast.Call) and
                 qualname(n.func) in self._PALLAS_CALL]
        if not sites:
            return
        oracle = self._oracle_decl(mod)
        if oracle is None:
            yield self.finding(
                mod, sites[0],
                "module calls pl.pallas_call but declares no "
                "_PALLAS_ORACLE = \"<parity test path>\" constant")
        elif not os.path.exists(os.path.join(self._repo_root(mod), oracle)):
            yield self.finding(
                mod, sites[0],
                f"_PALLAS_ORACLE points at {oracle!r}, which does not "
                "exist — the interpret-vs-XLA parity oracle moved or "
                "was never written")
        for call in sites:
            kw = next((k for k in call.keywords if k.arg == "interpret"),
                      None)
            if kw is None:
                yield self.finding(
                    mod, call,
                    "pallas_call without interpret= forwards: the kernel "
                    "can never run on CPU (the test platform); "
                    "thread an `interpret` parameter through the builder")
                continue
            if isinstance(kw.value, ast.Constant):
                yield self.finding(
                    mod, call,
                    f"pallas_call hard-codes interpret={kw.value.value!r}; "
                    "forward the builder's `interpret` parameter so the "
                    "dispatch gate can pick per-backend behavior")
                continue
            fn = self._enclosing_fn(mod, call)
            params = ({a.arg for a in func_params(fn)}
                      if fn is not None else set())
            names = {n.id for n in ast.walk(kw.value)
                     if isinstance(n, ast.Name)}
            if fn is None or not (names & params):
                yield self.finding(
                    mod, call,
                    "pallas_call's interpret= does not come from an "
                    "enclosing builder parameter — the lru_cached "
                    "`_build(..., interpret)` seam is the contract "
                    "(pallas_codec.py)")


class UnclassifiedDeviceDispatchRule(Rule):
    """unclassified-device-dispatch: a bare or broad `except` (bare,
    `Exception`, `BaseException`) wrapped around a device dispatch site
    must CLASSIFY the failure into the compute-fault taxonomy
    (`parallel.guard.classify` / the ComputeError subclasses) or
    re-raise — swallowing an `XlaRuntimeError` untyped is exactly the
    silent degradation the guarded dispatch layer exists to prevent
    (a device OOM absorbed by `except Exception: return None` never
    reaches the breaker, the quarantine, or the telemetry that names
    the degraded route).

    A *device dispatch site* inside the `try` body is any of:
      1. a `pl.pallas_call` invocation;
      2. a call to a function this module hands to jax.jit (the
         find_traced discovery the whole rule family shares);
      3. a call THROUGH the repo's jit-builder idiom: `fn = _build(...)`
         then `fn(...)` (or directly `_build(...)(args)`) where
         `_build` returns `jax.jit(...)` or is decorated with
         `telemetry.jit_builder` / `guard.guarded_builder`.

    A broad handler is compliant when it re-raises (any `raise`) or
    references the taxonomy (`classify`, `ComputeError`, `CompileError`,
    `DeviceOOM`, `KernelFault`, `DispatchTimeout`) — the guard seam
    itself is the canonical negative: its broad handler funnels every
    exception through `classify()` and re-raises the unclassifiable.
    """

    id = "unclassified-device-dispatch"
    severity = "error"
    requires_import = "jax"
    dirs = ("ops", "parallel", "storage", "query")

    _PALLAS_CALL = UnguardedPallasDispatchRule._PALLAS_CALL
    _BROAD = {"Exception", "BaseException"}
    _TAXONOMY = {"classify", "ComputeError", "CompileError", "DeviceOOM",
                 "KernelFault", "DispatchTimeout"}
    _BUILDER_DECOS = {"telemetry.jit_builder", "jit_builder",
                      "guard.guarded_builder", "guarded_builder",
                      "pguard.guarded_builder"}

    @staticmethod
    def _is_broad(handler: ast.ExceptHandler) -> bool:
        t = handler.type
        if t is None:
            return True
        names = [t] if not isinstance(t, ast.Tuple) else list(t.elts)
        return any(qualname(n).rsplit(".", 1)[-1] in
                   UnclassifiedDeviceDispatchRule._BROAD for n in names)

    def _is_compliant(self, handler: ast.ExceptHandler) -> bool:
        for node in ast.walk(handler):
            if isinstance(node, ast.Raise):
                return True
            if isinstance(node, ast.Name) and node.id in self._TAXONOMY:
                return True
            if isinstance(node, ast.Attribute) and \
                    node.attr in self._TAXONOMY:
                return True
        return False

    def _is_jit_builder(self, fn: ast.FunctionDef) -> bool:
        for dec in fn.decorator_list:
            d = dec.func if isinstance(dec, ast.Call) else dec
            if qualname(d) in self._BUILDER_DECOS:
                return True
        for node in ast.walk(fn):
            if isinstance(node, ast.Return) and \
                    isinstance(node.value, ast.Call) and \
                    _is_jax_jit(node.value.func):
                return True
        return False

    def _builder_vars(self, mod: Module, try_node: ast.Try,
                      by_name) -> Set[str]:
        """Names bound (in the enclosing function, before the try) from
        a call to a jit-builder — the `fn = _plan_executable(...)`
        idiom."""
        cur = mod.parents.get(try_node)
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            cur = mod.parents.get(cur)
        scope = cur if cur is not None else mod.tree
        out: Set[str] = set()
        for node in ast.walk(scope):
            if not (isinstance(node, ast.Assign) and
                    isinstance(node.value, ast.Call) and
                    node.lineno <= try_node.lineno):
                continue
            callee = node.value.func
            target = (_resolve(callee.id, node.lineno, by_name)
                      if isinstance(callee, ast.Name) else None)
            if target is not None and self._is_jit_builder(target):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
        return out

    def _dispatch_site(self, mod: Module, try_node: ast.Try,
                       traced, by_name) -> Optional[ast.Call]:
        builder_vars = None  # computed lazily (scope walk is not free)
        for stmt in try_node.body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                q = qualname(node.func)
                if q in self._PALLAS_CALL:
                    return node
                if isinstance(node.func, ast.Name):
                    target = _resolve(node.func.id, node.lineno, by_name)
                    if target is not None and (
                            id(target) in traced or
                            self._is_jit_builder(target)):
                        return node
                    if builder_vars is None:
                        builder_vars = self._builder_vars(
                            mod, try_node, by_name)
                    if node.func.id in builder_vars:
                        return node
                if isinstance(node.func, ast.Call) and \
                        isinstance(node.func.func, ast.Name):
                    target = _resolve(node.func.func.id,
                                      node.lineno, by_name)
                    if target is not None and self._is_jit_builder(target):
                        return node
        return None

    def check(self, mod: Module) -> Iterator[Finding]:
        tries = [n for n in ast.walk(mod.tree) if isinstance(n, ast.Try)]
        if not tries:
            return
        traced = find_traced(mod)
        by_name = _index_all_functions(mod)
        for t in tries:
            bad = [h for h in t.handlers
                   if self._is_broad(h) and not self._is_compliant(h)]
            if not bad:
                continue
            site = self._dispatch_site(mod, t, traced, by_name)
            if site is None:
                continue
            for h in bad:
                yield self.finding(
                    mod, h,
                    "broad except around a device dispatch (jit/pallas "
                    f"call at line {site.lineno}) neither classifies "
                    "into the ComputeError taxonomy nor re-raises — "
                    "route it through parallel.guard.classify (or "
                    "dispatch via guard.dispatch) so device faults "
                    "reach the breaker/quarantine/telemetry plane")


RULES: List[Rule] = [JaxPurityRule(), NonStaticJitCacheRule(),
                     ItemInLoopRule(), MeshSpecRule(),
                     UnguardedPallasDispatchRule(),
                     UnclassifiedDeviceDispatchRule()]
