"""Module indexing + traced-region call graph for the invariant linter.

The linter's rules need to know, for every function in the repo, whether it
can execute under a JAX trace (R1 host-sync, R5 precision) and whether it
*launches* compiled work (the dispatch-loop taint analysis).  This module
builds that knowledge from the AST alone:

- :class:`ModuleIndex` parses one file and records every function
  (including nested defs and lambdas), resolves call targets through the
  import aliases and local scopes (``rgcn_mod.encode_packed`` ->
  ``repro.core.rgcn.encode_packed``, ``self._make_step`` ->
  ``Class._make_step``), and marks *trace entries*: functions decorated
  with / passed to ``jax.jit`` / ``vmap`` / ``lax.scan`` / ``pallas_call``
  and friends;
- :func:`build_graph` links the per-module indexes into one call graph and
  runs two fixed points: **traced** (a callee of a traced function is
  traced) and **dispatching** (a function that directly or transitively
  invokes a compiled executable).

Both properties deliberately over-approximate — a function reachable from
a traced region is treated as traced even if some call sites are host-only.
That is the point of the waiver syntax (``# lint: allow[R1] reason``): the
analysis stays sound and the human records why an exception is genuine.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Optional

#: callables whose function-valued arguments (or decorated functions) run
#: under a JAX trace
TRACERS = {
    "jax.jit",
    "jax.vmap",
    "jax.pmap",
    "jax.grad",
    "jax.value_and_grad",
    "jax.checkpoint",
    "jax.remat",
    "jax.custom_vjp",
    "jax.custom_jvp",
    "jax.lax.scan",
    "jax.lax.map",
    "jax.lax.fori_loop",
    "jax.lax.while_loop",
    "jax.lax.cond",
    "jax.lax.switch",
    "jax.lax.associative_scan",
    "jax.experimental.pallas.pallas_call",
    "jax.shard_map",
}

#: fully-qualified fids of kernel-package entry points that must ALWAYS be
#: trace entries.  Decorator detection (functools.partial(jax.jit, ...) /
#: jax.custom_vjp) already finds these today; the explicit registry pins
#: them so a refactor of the decorator spelling can't silently drop a
#: Pallas launch out of the traced fixed point (R1/R5 would then stop
#: looking inside it).
KERNEL_ENTRIES = {
    "repro.kernels.rgcn_fused.kernel:rgcn_fused_flat_fwd",
    "repro.kernels.rgcn_fused.ops:rgcn_fused_agg_flat",
    "repro.kernels.rgcn_fused.ops:fused_two_level_readout",
}

#: fully-qualified fids of the trace->graph ingestion roots (the dual of
#: KERNEL_ENTRIES): these run the numpy RNG tracer on HOST threads — on
#: pool workers via ``pool.submit`` — and must NEVER become reachable from
#: a jit/scan/vmap trace (the tracer's bit-exact RNG stream contract dies
#: the moment it runs under a trace).  ``build_graph`` pins them as
#: ``host_entry`` and R1 flags any of them that the traced fixed point
#: reaches.  The ``.submit`` hop itself is a call edge (see visit_Call),
#: so the worker-side bodies stay inside the R1-R5 fixed points.
INGEST_ENTRIES = {
    "repro.ingest.engine:IngestEngine.iter_graphs",
    "repro.ingest.engine:IngestEngine._build_one",
    "repro.tracing.tracer:trace_kernel",
    "repro.tracing.tracer:trace_kernel_loop",
}

#: tracers whose FIRST positional argument is not the traced function
#: (the traced callable sits at these positions instead)
_TRACER_FN_POS = {
    "jax.lax.fori_loop": (2,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": (1,),
}


def dotted(node: ast.AST) -> Optional[list[str]]:
    """Flatten ``a.b.c`` into ``["a", "b", "c"]`` (None if not a pure
    name/attribute chain)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return parts[::-1]


@dataclass
class FunctionInfo:
    """One function/lambda, with everything the rules need to know."""

    fid: str                       # "repro.core.train:ContrastiveTrainer.fit"
    module: str
    path: str
    qual: str
    node: ast.AST                  # FunctionDef / AsyncFunctionDef / Lambda
    cls: Optional[str] = None      # enclosing class name, if a method
    calls: set = field(default_factory=set)          # resolved callee ids
    traced_entry: bool = False     # decorated with / passed to a tracer
    host_entry: bool = False       # registered host-only ingestion root
    lru_cached: bool = False       # functools.lru_cache/cache decorated
    returns_jit: bool = False      # returns a jax.jit(...) result
    donate_positions: tuple = ()   # donate_argnums of the returned jit
    traced: bool = False           # fixed-point result
    dispatching: bool = False      # fixed-point result


class ModuleIndex(ast.NodeVisitor):
    """Per-file AST index; see module docstring."""

    def __init__(self, path: str, module: str, tree: ast.Module):
        self.path = path
        self.module = module
        self.tree = tree
        self.functions: dict[str, FunctionInfo] = {}
        self.imports: dict[str, str] = {}
        #: attribute names ever assigned a jax.jit(...) result anywhere in
        #: the repo-wide scan (self._embed_fn, EngineFns(scan=...)); used as
        #: a tail-match fallback when full resolution fails
        self.jit_attrs: dict[str, tuple] = {}   # attr name -> donate positions
        #: resolution of every Call node's callee to a dotted string
        self.call_names: dict[ast.Call, Optional[str]] = {}
        #: pool.submit(fn, ...) call -> resolved worker fn (rules use this
        #: to treat a future of compiled work as a dispatch source)
        self.submit_targets: dict[ast.Call, Optional[str]] = {}
        #: per-function local names bound to jitted callables -> donate pos
        self.jit_locals: dict[str, dict[str, tuple]] = {}
        self._scopes: list[dict] = [{}]
        self._quals: list[str] = []
        self._cls: list[str] = []
        self._fn: list[FunctionInfo] = []
        self._prescan(tree)
        self.visit(tree)

    # -- symbol tables -------------------------------------------------------
    def _prescan(self, tree: ast.Module) -> None:
        """Module-level names must resolve regardless of definition order."""
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scopes[0][node.name] = ("func", node.name)
            elif isinstance(node, ast.ClassDef):
                self._scopes[0][node.name] = ("class", node.name)

    def _bind(self, name: str, ref: tuple) -> None:
        self._scopes[-1][name] = ref

    def _lookup(self, name: str) -> Optional[tuple]:
        for scope in reversed(self._scopes):
            if name in scope:
                return scope[name]
        if name in self.imports:
            return ("ext", self.imports[name])
        return None

    def _qual(self, name: str) -> str:
        return ".".join(self._quals + [name]) if self._quals else name

    def _fid(self, qual: str) -> str:
        return f"{self.module}:{qual}"

    # -- name resolution -----------------------------------------------------
    def resolve(self, node: ast.AST) -> Optional[str]:
        """Resolve a callee expression to a dotted string: either an
        external path ("jax.lax.scan", "numpy.asarray") or a local id
        ("<module>:<qual>").  ``self.x`` resolves within the enclosing
        class; ``functools.partial(f, ...)`` unwraps to ``f``, as does
        ``repro.telemetry.carry(f)`` (``f`` run under the caller's span
        context)."""
        if isinstance(node, ast.Call):  # partial(f, ...) / jit(f) chains
            inner = self.resolve(node.func)
            if inner in ("functools.partial", "jax.jit", "jax.vmap",
                         "jax.pmap", "jax.checkpoint", "jax.remat",
                         "repro.telemetry.carry"):
                for arg in node.args:
                    r = self.resolve(arg)
                    if r is not None:
                        return r
            return None
        parts = dotted(node)
        if parts is None:
            return None
        base, rest = parts[0], parts[1:]
        if base == "self" and self._cls and rest:
            return self._fid(f"{self._cls[-1]}.{rest[0]}")
        ref = self._lookup(base)
        if ref is None:
            return None
        kind, target = ref
        if kind == "ext":
            return ".".join([target] + rest)
        if kind == "func":
            return self._fid(target) if not rest else None
        if kind == "class":
            return self._fid(".".join([target] + rest)) if rest else None
        return None

    def _resolve_local_function(self, node: ast.AST) -> Optional[str]:
        """Like resolve(), but only returns ids of functions defined in
        this module (the targets tracer arguments may mark as traced)."""
        r = self.resolve(node)
        if r is not None and r.startswith(f"{self.module}:"):
            return r
        return None

    # -- visitors ------------------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.imports[a.asname or a.name.split(".")[0]] = (
                a.name if a.asname else a.name.split(".")[0])

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module is None or node.level:
            return
        for a in node.names:
            self.imports[a.asname or a.name] = f"{node.module}.{a.name}"

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._cls.append(node.name)
        self._quals.append(node.name)
        self._scopes.append({})
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scopes[-1][item.name] = (
                    "func", f"{node.name}.{item.name}")
        self.generic_visit(node)
        self._scopes.pop()
        self._quals.pop()
        self._cls.pop()

    def _enter_function(self, node, name: str) -> FunctionInfo:
        qual = self._qual(name)
        info = FunctionInfo(
            fid=self._fid(qual), module=self.module, path=self.path,
            qual=qual, node=node, cls=self._cls[-1] if self._cls else None)
        self.functions[qual] = info
        self.jit_locals[info.fid] = {}
        return info

    def _handle_decorators(self, node, info: FunctionInfo) -> None:
        for dec in node.decorator_list:
            name = self.resolve(dec.func if isinstance(dec, ast.Call)
                                else dec)
            if isinstance(dec, ast.Call) and name == "functools.partial" \
                    and dec.args:
                # functools.partial(jax.jit, static_argnames=...) decorator
                name = self.resolve(dec.args[0])
            if name in TRACERS:
                info.traced_entry = True
            if name in ("functools.lru_cache", "functools.cache"):
                info.lru_cached = True

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_function(node, node.name)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_function(node, node.name)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._visit_function(node, f"<lambda:{node.lineno}>")

    def _visit_function(self, node, name: str) -> None:
        info = self._enter_function(node, name)
        if not isinstance(node, ast.Lambda):
            self._handle_decorators(node, info)
        if self._quals:  # nested defs resolve by name in the parent scope
            self._scopes[-1].setdefault(name, ("func", info.qual))
        self._quals.append(name)
        self._scopes.append({})
        self._fn.append(info)
        # prescan sibling-order-independent nested defs
        body = node.body if isinstance(node.body, list) else [node.body]
        for item in body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scopes[-1][item.name] = (
                    "func", f"{info.qual}.{item.name}")
        for item in body:
            self.visit(item)
        if not isinstance(node, ast.Lambda):
            self._finish_function(node, info)
        self._fn.pop()
        self._scopes.pop()
        self._quals.pop()

    def _finish_function(self, node, info: FunctionInfo) -> None:
        """Mark returns-jitted functions (their call results are compiled
        executables — dispatch/donation sources at the call site)."""
        locals_jit = self.jit_locals[info.fid]
        for ret in ast.walk(node):
            if not isinstance(ret, ast.Return) or ret.value is None:
                continue
            val = ret.value
            if isinstance(val, ast.Call) and self._is_jit_call(val):
                info.returns_jit = True
                info.donate_positions = self._donate_positions(val)
            elif isinstance(val, ast.Name) and val.id in locals_jit:
                info.returns_jit = True
                info.donate_positions = locals_jit[val.id]
            elif isinstance(val, ast.Attribute) and val.attr in self.jit_attrs:
                info.returns_jit = True
                info.donate_positions = self.jit_attrs[val.attr]

    # -- call / assignment analysis -----------------------------------------
    def _is_jit_call(self, node: ast.Call) -> bool:
        return self.resolve(node.func) == "jax.jit"

    @staticmethod
    def _donate_positions(node: ast.Call) -> tuple:
        for kw in node.keywords:
            if kw.arg == "donate_argnums":
                if isinstance(kw.value, ast.Tuple):
                    return tuple(e.value for e in kw.value.elts
                                 if isinstance(e, ast.Constant))
                if isinstance(kw.value, ast.Constant):
                    return (kw.value.value,)
        return ()

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)
        value = node.value
        if isinstance(value, ast.Call) and self._is_jit_call(value):
            donate = self._donate_positions(value)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and self._fn:
                    self.jit_locals[self._fn[-1].fid][tgt.id] = donate
                elif isinstance(tgt, ast.Attribute):
                    self.jit_attrs[tgt.attr] = donate
        # alias: name = other_local_function / partial(fn, ...)
        target_ref = None
        if isinstance(value, (ast.Name, ast.Attribute)):
            r = self._resolve_local_function(value)
            if r is not None:
                target_ref = ("func", r.split(":", 1)[1])
        elif isinstance(value, ast.Call):
            base = self.resolve(value.func)
            if base == "functools.partial" and value.args:
                r = self._resolve_local_function(value.args[0])
                if r is not None:
                    target_ref = ("func", r.split(":", 1)[1])
        if target_ref is not None:
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self._bind(tgt.id, target_ref)

    def visit_Call(self, node: ast.Call) -> None:
        self.generic_visit(node)
        name = self.resolve(node.func)
        self.call_names[node] = name
        if self._fn:
            fn = self._fn[-1]
            if name is not None:
                fn.calls.add(name)
            # jit-attr construction through keywords:
            #   EngineFns(scan=jax.jit(chunk, donate_argnums=(0,)))
            for kw in node.keywords:
                if (kw.arg and isinstance(kw.value, ast.Call)
                        and self._is_jit_call(kw.value)):
                    self.jit_attrs[kw.arg] = self._donate_positions(kw.value)
        # worker-pool hop: pool.submit(fn, ...) runs fn on an executor
        # thread.  The pool object is an unresolvable local (name is None
        # here — our OWN .submit methods resolve above and keep their
        # normal edge), so record the worker fn as a callee: the traced /
        # dispatching fixed points then see through the executor instead
        # of losing the body at the thread boundary.
        if (name is None and node.args
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "submit"):
            target = self.resolve(node.args[0])
            self.submit_targets[node] = target
            if target is not None and self._fn:
                self._fn[-1].calls.add(target)
        if name in TRACERS:
            positions = _TRACER_FN_POS.get(name, (0,))
            for pos in positions:
                if pos < len(node.args):
                    self._mark_traced_target(node.args[pos])
            # jax.jit(f)(...) nests: inner vmap/partial calls get their own
            # visit, so only direct args need handling here

    def _mark_traced_target(self, arg: ast.AST) -> None:
        fid = None
        if isinstance(arg, ast.Lambda):
            fid = self._fid(self._qual(f"<lambda:{arg.lineno}>"))
        elif isinstance(arg, ast.Call):
            inner = self.resolve(arg.func)
            if inner == "functools.partial" and arg.args:
                fid = self._resolve_local_function(arg.args[0])
            elif inner in ("jax.vmap", "jax.jit", "jax.checkpoint",
                           "jax.remat") and arg.args:
                fid = self._resolve_local_function(arg.args[0])
        else:
            fid = self._resolve_local_function(arg)
        if fid is not None:
            qual = fid.split(":", 1)[1]
            if qual in self.functions:
                self.functions[qual].traced_entry = True


def index_module(path: str, module: str, source: str) -> ModuleIndex:
    return ModuleIndex(path, module, ast.parse(source, filename=path))


def build_graph(indexes: list[ModuleIndex]) -> dict[str, FunctionInfo]:
    """Link per-module indexes and run the traced/dispatching fixed points.
    Returns the global fid -> FunctionInfo map (mutated in place)."""
    funcs: dict[str, FunctionInfo] = {}
    modnames = set()
    for idx in indexes:
        modnames.add(idx.module)
        for info in idx.functions.values():
            funcs[info.fid] = info
    for fid in KERNEL_ENTRIES:      # registered kernel launches (see above)
        if fid in funcs:
            funcs[fid].traced_entry = True
    for fid in INGEST_ENTRIES:      # registered host-only ingestion roots
        if fid in funcs:
            funcs[fid].host_entry = True

    def to_fid(callee: str) -> Optional[str]:
        """Map a resolved dotted path to a known function id."""
        if callee in funcs:
            return callee
        if ":" in callee:
            return None
        # external-style path into a scanned module: repro.core.rgcn.encode
        parts = callee.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            mod = ".".join(parts[:cut])
            if mod in modnames:
                fid = f"{mod}:{'.'.join(parts[cut:])}"
                return fid if fid in funcs else None
        return None

    edges: dict[str, set] = {}
    for info in funcs.values():
        edges[info.fid] = set()
        for callee in info.calls:
            fid = to_fid(callee)
            if fid is not None:
                edges[info.fid].add(fid)

    # traced: trace entries + everything they (transitively) call
    work = [f.fid for f in funcs.values() if f.traced_entry]
    for fid in work:
        funcs[fid].traced = True
    while work:
        fid = work.pop()
        for callee in edges[fid]:
            if not funcs[callee].traced:
                funcs[callee].traced = True
                work.append(callee)

    # dispatching: launches compiled work (directly or transitively)
    jit_attr_names = set()
    for idx in indexes:
        jit_attr_names.update(idx.jit_attrs)
    for idx in indexes:
        for info in idx.functions.values():
            if info.dispatching:
                continue
            for callee in info.calls:
                fid = to_fid(callee)
                if fid is not None and (funcs[fid].traced_entry
                                        or funcs[fid].returns_jit):
                    info.dispatching = True
                    break
    changed = True
    while changed:
        changed = False
        for info in funcs.values():
            if info.dispatching:
                continue
            for callee in edges[info.fid]:
                if funcs[callee].dispatching:
                    info.dispatching = True
                    changed = True
                    break
    return funcs
