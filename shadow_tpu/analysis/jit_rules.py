"""jit_rules: the compile-surface model + the SIM3xx catalog.

Every remaining wall of this platform is a device-plane fact — per-launch
cost (~320 us, size-independent at our widths), jit-cache stability (the
fleet's zero-recompile detach/re-arm contract, ``fleet.compiles``), and
first-compile cost (20-40 s on accelerator boxes).  Those contracts were
enforced only at RUNTIME, after the wall is paid.  simjit makes the
compile surface a lint-time contract: a package-wide model resolves every
jit program identity — ``jax.jit(f, ...)``, ``@partial(jax.jit, ...)``,
vmapped/shard_map-wrapped variants, factory functions returning jits, and
the variant caches (device_plane's <=4-compile sharded-variant cache, the
fleet's sticky-width classes) — and five rules run over it:

=======  ========  ====================================================
rule     severity  invariant guarded
=======  ========  ====================================================
SIM301   error     no recompile hazard: static args fed from varying
                   shape-deriving sources, operand widths derived
                   per-call outside the pad/bucket contract, traced
                   closures over loop-varying Python values
SIM302   error     no implicit host<->device sync inside the pipelined
                   dispatch window: ``.item()``, ``float()/int()/
                   bool()`` on a device value, ``np.asarray`` of a live
                   jit result, traced-value branching — each silently
                   serializes the PR-1 async overlap
SIM303   error     dtype-promotion drift against the non-negative int64
                   contract in kernel-tagged files (true division /
                   float-literal arithmetic / float casts on sim-time
                   lanes — extends SIM204's carrier tracking to
                   arithmetic)
SIM304   error     donation misuse beyond SIM004: one donated jit
                   shared by two call-site owners, or donation pinned
                   to the CPU backend (the PR-1 copy+sync trap)
SIM305   error     compile-budget drift: the statically enumerated
                   compile-key count per module must EQUAL the
                   checked-in [tool.simjit.budget] table, unbounded
                   in-function jit creation is always a finding, and
                   literal cache caps must match their declared budget
=======  ========  ====================================================

The model is deliberately scoped to stay sound-ish without whole-program
dataflow: program identities resolve through module/class assignments,
``self`` attribute handles (``self._flush_step =
step_window_flush_for_backend()``), factory returns, and import aliases
(ModuleContext.resolve); device-value tracking for SIM302 is
per-function (a name assigned from a jit call or a ``jnp.*`` op is a
device value until explicitly synced); and the budget's unit is the JIT
PROGRAM IDENTITY (python-level compiled-callable objects), with bounded
variant caches contributing their literal cap — the runtime caches
(``fleet.compiles``, the sharded-variant dict) are cross-checked against
the same table by ``simfleet smoke``.
"""

from __future__ import annotations

import ast
import fnmatch
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from .simlint import Config, Finding, ModuleContext
from .twin_rules import _is_timey

# jax.jit spellings ModuleContext.resolve canonicalizes to
_JIT_NAMES = ("jax.jit", "jax.api.jit")
_PARTIAL_NAMES = ("functools.partial", "partial")
# transform wrappers a jit may trace through: jax.jit(jax.vmap(f)),
# jax.jit(shard_map(f, ...)) — the traced fn is the wrapped one
_TRANSFORM_NAMES = ("jax.vmap", "jax.shard_map", "shard_map", "jax.pmap")
# the pad/bucket contract: a width that went through one of these is
# drawn from a bounded class set, so it cannot churn the jit cache
_PAD_CONTRACT_RE = re.compile(r"pad|pow2|bucket", re.IGNORECASE)
# shape-deriving calls/attrs that vary per call site
_SHAPE_FNS = {"len"}
# numpy/jnp array constructors whose FIRST argument is a width
_WIDTH_CTORS = {"zeros", "ones", "empty", "full", "arange"}
# python scalar coercions that force a host<->device sync on a device value
_SYNC_COERCIONS = {"float", "int", "bool"}
# numpy entry points that pull a device buffer to the host
_NP_PULLS = {"numpy.asarray", "numpy.array", "numpy.ascontiguousarray",
             "numpy.copy"}
_FLOAT_DTYPES = {"float32", "float64", "float16", "bfloat16"}


# ---------------------------------------------------------------------------
# jit expression parsing


@dataclass
class JitSpec:
    """One parsed jax.jit(...) / partial(jax.jit, ...) expression."""
    static_argnums: Set[int] = field(default_factory=set)
    static_argnames: Set[str] = field(default_factory=set)
    donate_argnums: Set[int] = field(default_factory=set)
    backend: Optional[str] = None
    dynamic_static: bool = False     # static_argnums was not a literal
    fn_node: Optional[ast.AST] = None  # the traced callable expression


def _int_set(node: ast.AST) -> Optional[Set[int]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[int] = set()
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, int)):
                return None
            out.add(e.value)
        return out
    return None


def _str_set(node: ast.AST) -> Optional[Set[str]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, str)):
                return None
            out.add(e.value)
        return out
    return None


def _fill_spec(spec: JitSpec, call: ast.Call) -> None:
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = _int_set(kw.value)
            if v is None:
                spec.dynamic_static = True
            else:
                spec.static_argnums |= v
        elif kw.arg == "static_argnames":
            v2 = _str_set(kw.value)
            if v2 is None:
                spec.dynamic_static = True
            else:
                spec.static_argnames |= v2
        elif kw.arg == "donate_argnums":
            v = _int_set(kw.value)
            if v:
                spec.donate_argnums |= v
        elif kw.arg == "backend" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            spec.backend = kw.value.value


def _unwrap_transform(node: ast.AST, ctx: ModuleContext) -> ast.AST:
    """See through jax.vmap(f)/shard_map(f, ...) to the traced fn."""
    while isinstance(node, ast.Call):
        r = ctx.resolve(node.func)
        name = r[0] if r else (node.func.id if isinstance(node.func,
                                                          ast.Name) else "")
        if name in _TRANSFORM_NAMES or name.endswith(".vmap") \
                or name.endswith("shard_map"):
            if node.args:
                node = node.args[0]
                continue
        break
    return node


def parse_jit_expr(node: ast.AST, ctx: ModuleContext) -> Optional[JitSpec]:
    """JitSpec if ``node`` is a jit-program-producing expression:
    ``jax.jit(f, ...)``, ``partial(jax.jit, ...)`` (decorator form, no
    fn), or ``partial(jax.jit, ...)(f)`` (the ops/ idiom)."""
    if not isinstance(node, ast.Call):
        return None
    r = ctx.resolve(node.func)
    if r is not None and r[0] in _JIT_NAMES:
        spec = JitSpec()
        _fill_spec(spec, node)
        if node.args:
            spec.fn_node = _unwrap_transform(node.args[0], ctx)
        return spec
    is_partial = (r is not None and r[0] in _PARTIAL_NAMES) or (
        isinstance(node.func, ast.Name) and node.func.id == "partial")
    if is_partial and node.args:
        inner = ctx.resolve(node.args[0])
        if inner is not None and inner[0] in _JIT_NAMES:
            spec = JitSpec()
            _fill_spec(spec, node)
            if len(node.args) > 1:
                spec.fn_node = _unwrap_transform(node.args[1], ctx)
            return spec
    # partial(jax.jit, ...)(fn): the OUTER call applies the wrapper
    inner_spec = parse_jit_expr(node.func, ctx)
    if inner_spec is not None:
        if node.args:
            inner_spec.fn_node = _unwrap_transform(node.args[0], ctx)
        return inner_spec
    return None


# ---------------------------------------------------------------------------
# the per-module jit surface


@dataclass
class JitProgram:
    """One jit program identity (a python-level compiled callable)."""
    name: str                 # qualname within its module ("Cls.attr" ok)
    relpath: str
    line: int
    spec: JitSpec
    scope: str                # "module" | "class" | "function"
    owner: Optional[str] = None      # enclosing function qualname
    traced_def: Optional[ast.AST] = None   # the FunctionDef it traces
    cache_cap: Optional[int] = None  # literal bound when cache-guarded
    attr_store: bool = False  # held on an object attribute (replacement
    #                           semantics: one live identity per attr)


def _qualname(ctx: ModuleContext, node: ast.AST) -> Tuple[str, Optional[str]]:
    """(scope, enclosing function qualname) for a node: walks parents."""
    parts: List[str] = []
    fn_qual: Optional[str] = None
    cur = ctx.parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            parts.append(cur.name)
            if fn_qual is None:
                fn_qual = cur.name
        elif isinstance(cur, ast.ClassDef):
            parts.append(cur.name)
        cur = ctx.parent(cur)
    if fn_qual is not None:
        rest = [p for p in reversed(parts)]
        return "function", ".".join(rest)
    if parts:
        return "class", None
    return "module", None


def _cache_cap_for(ctx: ModuleContext, node: ast.AST) -> Optional[int]:
    """A literal variant-cache bound guarding ``node``: the enclosing
    function contains ``len(X) >= N`` / ``len(X) < N`` with the jit
    creation on the bounded side — the _pick_sharded_step idiom.  The
    cap found is N (+1 for the always-present full program is the
    caller's business)."""
    fn = ctx.enclosing_function(node)
    if fn is None:
        return None
    for n in ast.walk(fn):
        if not (isinstance(n, ast.Compare) and len(n.ops) == 1):
            continue
        left, op, right = n.left, n.ops[0], n.comparators[0]
        if isinstance(left, ast.Call) and isinstance(left.func, ast.Name) \
                and left.func.id == "len" \
                and isinstance(right, ast.Constant) \
                and isinstance(right.value, int) \
                and isinstance(op, (ast.GtE, ast.Lt, ast.LtE, ast.Gt)):
            return right.value
    return None


class ModuleJits:
    """The jit surface of one module: programs, factories, handles,
    traced defs, and resolved call sites."""

    def __init__(self, ctx: ModuleContext):
        self.ctx = ctx
        self.programs: Dict[str, JitProgram] = {}
        # functions whose return value is a jit program (factories);
        # qualname -> the JitSpec of the returned program
        self.factories: Dict[str, JitSpec] = {}
        # obj.<attr> names holding a program or a factory() result; may
        # include BORROWED entries (stored by another module) after the
        # package link pass — those resolve call sites but never count
        # toward this module's compile budget (only ``programs`` does)
        self.handles: Dict[str, JitProgram] = {}
        # obj.<attr> names holding a FACTORY itself (the
        # ``plane._mesh_make_step = make_step`` idiom): calling one
        # mints a program
        self.attr_factories: Dict[str, JitSpec] = {}
        # factory names consumed by a store/creation in this module
        # (their identities are counted at the store, not as a floor)
        self.consumed_factories: Set[str] = set()
        # jit-traced function defs (for SIM301 closure + SIM303 scoping)
        self.traced: List[Tuple[ast.AST, JitProgram]] = []
        self._collect()
        # call sites are collected by JitPackage AFTER the cross-module
        # link pass settles (imported factories, borrowed attr handles)
        self.call_sites: List[Tuple[JitProgram, ast.Call,
                                    Optional[str], str]] = []

    # -- collection --------------------------------------------------------

    def _local_functions(self) -> Dict[str, ast.AST]:
        out: Dict[str, ast.AST] = {}
        for node in self.ctx.walk(ast.FunctionDef, ast.AsyncFunctionDef):
            out.setdefault(node.name, node)
        return out

    def _collect(self) -> None:
        ctx = self.ctx
        local_fns = self._local_functions()

        def add_program(name: str, node: ast.AST, spec: JitSpec,
                        traced: Optional[ast.AST]) -> JitProgram:
            scope, owner = _qualname(ctx, node)
            prog = JitProgram(name, ctx.relpath,
                              getattr(node, "lineno", 1), spec, scope,
                              owner, traced)
            if scope == "function":
                prog.cache_cap = _cache_cap_for(ctx, node)
            self.programs[name] = prog
            if traced is not None:
                self.traced.append((traced, prog))
            return prog

        # decorated defs
        for fn in ctx.walk(ast.FunctionDef, ast.AsyncFunctionDef):
            for dec in fn.decorator_list:
                spec = None
                if isinstance(dec, ast.Call):
                    spec = parse_jit_expr(dec, ctx)
                else:
                    r = ctx.resolve(dec)
                    if r is not None and r[0] in _JIT_NAMES:
                        spec = JitSpec()
                if spec is not None:
                    add_program(fn.name, fn, spec, fn)
                    break
        # assignments: name = jit_expr / self.attr = jit_expr
        for node in ctx.walk(ast.Assign):
            if len(node.targets) != 1:
                continue
            tgt = node.targets[0]
            spec = parse_jit_expr(node.value, ctx)
            traced = None
            if spec is not None and spec.fn_node is not None \
                    and isinstance(spec.fn_node, ast.Name):
                traced = local_fns.get(spec.fn_node.id)
            if spec is None:
                continue
            if isinstance(tgt, ast.Name):
                add_program(tgt.id, node, spec, traced)
            elif isinstance(tgt, ast.Attribute) and \
                    isinstance(tgt.value, ast.Name):
                prog = add_program(tgt.attr, node, spec, traced)
                prog.attr_store = True
                self.handles[tgt.attr] = prog
        # factories: functions returning a jit expr or a program name —
        # ALL returns are merged (the backend-picking factory returns
        # the donating program on accelerators and the non-donating twin
        # on cpu: the merged spec donates only when every branch does)
        for fn in ctx.walk(ast.FunctionDef, ast.AsyncFunctionDef):
            specs: List[JitSpec] = []
            first_line = fn.lineno
            for node in ast.walk(fn):
                if not isinstance(node, ast.Return) or node.value is None:
                    continue
                spec = parse_jit_expr(node.value, ctx)
                if spec is None and isinstance(node.value, ast.Name) \
                        and node.value.id in self.programs:
                    spec = self.programs[node.value.id].spec
                if spec is not None:
                    specs.append(spec)
                    if not len(specs) - 1:
                        first_line = node.lineno
                    if spec.fn_node is not None \
                            and isinstance(spec.fn_node, ast.Name):
                        traced = local_fns.get(spec.fn_node.id)
                        if traced is not None and not any(
                                t is traced for t, _ in self.traced):
                            prog = JitProgram(
                                f"{fn.name}.<returned>", ctx.relpath,
                                node.lineno, spec, "function", fn.name,
                                traced)
                            self.traced.append((traced, prog))
            if not specs:
                continue
            merged = specs[0]
            if len(specs) > 1:
                merged = JitSpec()
                for s in specs:
                    merged.static_argnums |= s.static_argnums
                    merged.static_argnames |= s.static_argnames
                    merged.dynamic_static |= s.dynamic_static
                donate = specs[0].donate_argnums
                for s in specs[1:]:
                    donate = donate & s.donate_argnums
                merged.donate_argnums = donate
                backends = {s.backend for s in specs}
                merged.backend = backends.pop() if len(backends) == 1 \
                    else None
            self.factories[fn.name] = merged
        # handles: obj.attr = <program name>
        for node in ctx.walk(ast.Assign):
            if len(node.targets) != 1:
                continue
            tgt = node.targets[0]
            if not (isinstance(tgt, ast.Attribute) and
                    isinstance(tgt.value, ast.Name)):
                continue
            val = node.value
            if isinstance(val, ast.Name) and val.id in self.programs:
                self.handles.setdefault(tgt.attr, self.programs[val.id])

    # -- the package link pass ---------------------------------------------

    def link(self, factories_by_symbol: Dict[str, JitSpec],
             attr_factories: Dict[str, JitSpec],
             attr_handles: Dict[str, JitProgram]) -> bool:
        """One round of cross-module resolution: imported factories
        (``step_window_flush_for_backend`` called from device_plane),
        factory-valued attributes (``plane._mesh_make_step =
        make_step``), and borrowed attr handles (the device plane calls
        ``self._sharded_step`` that meshplane stored).  Returns True
        when anything new resolved — JitPackage iterates to fixpoint."""
        ctx = self.ctx
        changed = False

        def factory_spec(name: str) -> Optional[JitSpec]:
            if name in self.factories:
                return self.factories[name]
            spec = factories_by_symbol.get(name)
            if spec is None:
                return None
            target = ctx.aliases.get(name)
            if target is None or not target.endswith("." + name):
                return None     # bare-name collision, not an import
            return spec

        # new factories: a return calling a known factory
        for fn in ctx.walk(ast.FunctionDef, ast.AsyncFunctionDef):
            if fn.name in self.factories:
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Return) and \
                        isinstance(node.value, ast.Call) and \
                        isinstance(node.value.func, ast.Name):
                    spec = factory_spec(node.value.func.id)
                    if spec is not None:
                        self.factories[fn.name] = spec
                        self.consumed_factories.add(node.value.func.id)
                        changed = True
                        break

        for node in ctx.walk(ast.Assign):
            if len(node.targets) != 1:
                continue
            tgt, val = node.targets[0], node.value
            # obj.attr = factory(...)  -> a stored program identity
            # obj.attr = factory       -> a factory-valued attribute
            if isinstance(tgt, ast.Attribute) and \
                    isinstance(tgt.value, ast.Name):
                if isinstance(val, ast.Call) and \
                        isinstance(val.func, ast.Name):
                    spec = factory_spec(val.func.id)
                    if spec is not None and tgt.attr not in self.programs:
                        scope, owner = _qualname(ctx, node)
                        prog = JitProgram(tgt.attr, ctx.relpath,
                                          node.lineno, spec, scope, owner,
                                          attr_store=True)
                        self.programs[tgt.attr] = prog
                        self.handles[tgt.attr] = prog
                        self.consumed_factories.add(val.func.id)
                        changed = True
                elif isinstance(val, ast.Name):
                    spec = factory_spec(val.id)
                    if spec is not None and \
                            tgt.attr not in self.attr_factories:
                        self.attr_factories[tgt.attr] = spec
                        self.consumed_factories.add(val.id)
                        changed = True
            # local = obj.attr_factory(...)  -> a minted program (the
            # _pick_sharded_step variant-cache idiom)
            elif isinstance(tgt, ast.Name) and isinstance(val, ast.Call) \
                    and isinstance(val.func, ast.Attribute):
                spec = self.attr_factories.get(val.func.attr) or \
                    attr_factories.get(val.func.attr)
                if spec is not None:
                    scope, owner = _qualname(ctx, node)
                    key = f"{owner or '<module>'}.{tgt.id}"
                    if key not in self.programs:
                        prog = JitProgram(key, ctx.relpath, node.lineno,
                                          spec, scope, owner)
                        if scope == "function":
                            prog.cache_cap = _cache_cap_for(ctx, node)
                        self.programs[key] = prog
                        changed = True
        # borrow attr handles other modules stored, for call resolution
        for attr, prog in sorted(attr_handles.items()):
            if attr not in self.handles:
                self.handles[attr] = prog
                changed = True
        return changed

    def collect_calls(self) -> None:
        """(program, call node, enclosing function name, kind) for every
        resolvable jit-program call in this module: direct names
        (kind="name") and attr handles, own or borrowed (kind="handle").
        Factory calls mint programs and are NOT call sites."""
        out: List[Tuple[JitProgram, ast.Call, Optional[str], str]] = []
        ctx = self.ctx
        local_factories = set(self.factories)
        for call in ctx.walk(ast.Call):
            prog = None
            kind = "name"
            f = call.func
            if isinstance(f, ast.Name) and f.id in self.programs \
                    and f.id not in local_factories:
                prog = self.programs[f.id]
            elif isinstance(f, ast.Attribute) and f.attr in self.handles \
                    and f.attr not in self.attr_factories:
                prog = self.handles[f.attr]
                kind = "handle"
            if prog is None:
                continue
            fn = ctx.enclosing_function(call)
            out.append((prog, call, fn.name if fn is not None else None,
                        kind))
        self.call_sites = out


# ---------------------------------------------------------------------------
# the package model


class JitPackage:
    """All parsed modules + their jit surfaces + the simjit config
    (kernel-tagged globs, the [tool.simjit.budget] table)."""

    def __init__(self, contexts: List[ModuleContext],
                 config: Optional[Config] = None,
                 budget: Optional[Dict[str, int]] = None,
                 kernel: Optional[List[str]] = None):
        self.contexts = {c.relpath: c for c in contexts}
        self.config = config or Config()
        self.budget = dict(budget or {})
        self.kernel = list(kernel or [])
        self.modules: Dict[str, ModuleJits] = {}
        for rel, ctx in sorted(self.contexts.items()):
            self.modules[rel] = ModuleJits(ctx)
        # cross-module link to fixpoint: each round shares every
        # module's factories and attribute-stored handles with every
        # other module, so chains like exchange.make_mesh_span_flush ->
        # meshplane.make_step -> plane._mesh_make_step ->
        # device_plane._pick_sharded_step resolve (bounded rounds; the
        # tree's deepest chain is three hops)
        for _round in range(4):
            factories_by_symbol: Dict[str, JitSpec] = {}
            attr_factories: Dict[str, JitSpec] = {}
            attr_handles: Dict[str, JitProgram] = {}
            for rel, mj in sorted(self.modules.items()):
                for fname, spec in sorted(mj.factories.items()):
                    factories_by_symbol.setdefault(fname, spec)
                attr_factories.update(mj.attr_factories)
                for attr, prog in sorted(mj.handles.items()):
                    if prog.relpath == rel:     # own stores only
                        attr_handles.setdefault(attr, prog)
            changed = False
            for rel, mj in sorted(self.modules.items()):
                changed |= mj.link(factories_by_symbol, attr_factories,
                                   attr_handles)
            if not changed:
                break
        for rel, mj in sorted(self.modules.items()):
            mj.collect_calls()
        # package-wide donated-program registry keyed by symbol name so
        # imported call sites resolve (symbol names are unique here)
        self.by_symbol: Dict[str, List[JitProgram]] = {}
        for rel, mj in sorted(self.modules.items()):
            for name, prog in sorted(mj.programs.items()):
                self.by_symbol.setdefault(name.split(".")[-1],
                                          []).append(prog)

    def is_kernel(self, relpath: str) -> bool:
        return any(fnmatch.fnmatch(relpath, p) for p in self.kernel)

    def static_key_count(self, rel: str
                         ) -> Tuple[int, List[Tuple[JitProgram, str]]]:
        """(enumerable compile-key count, [(program, problem)]) for one
        module.  Each module/class-scope program identity is one key; a
        function-scope creation guarded by a literal cache cap
        contributes the cap; an unguarded function-scope creation is an
        unbounded-growth problem."""
        mj = self.modules.get(rel)
        if mj is None:
            return 0, []
        count = 0
        problems: List[Tuple[JitProgram, str]] = []
        seen: Set[int] = set()
        for name, prog in sorted(mj.programs.items()):
            if id(prog) in seen:
                continue
            seen.add(id(prog))
            if prog.scope in ("module", "class"):
                count += 1
            elif prog.attr_store or (
                    prog.owner is not None and
                    prog.owner.split(".")[-1] == "__init__"):
                # one live program per attribute / constructed object:
                # replacement semantics (self._x = factory() re-stores,
                # it doesn't accumulate identities)
                count += 1
            elif prog.cache_cap is not None:
                count += prog.cache_cap
            else:
                problems.append((prog, (
                    f"jit program `{name}` is created inside "
                    f"`{prog.owner}` with no literal cache bound — "
                    "every call mints a fresh compiled program "
                    "(unbounded compile-key growth); cache it with a "
                    "`len(cache) >= N` cap or hoist the creation")))
        # factory functions themselves are not keys (their stores are),
        # but a factory neither stored nor wrapped anywhere in ITS OWN
        # module is reachable only through consumers this module can't
        # see — count one key as the conservative floor so the defining
        # module keeps a budget presence
        stored = {p.name for p in mj.programs.values()}
        for fname in sorted(mj.factories):
            if fname in stored or fname in mj.consumed_factories:
                continue
            if any(p.owner == fname for p in mj.programs.values()):
                continue
            count += 1
        return count, problems


class JitRule:
    """One compile-surface invariant checked over the whole package."""

    id: str = "SIM300"
    severity: str = "error"
    short: str = ""

    def run(self, pkg: JitPackage) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, relpath: str, node: ast.AST, message: str) -> Finding:
        return Finding(self.id, self.severity, relpath,
                       getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message)


# ---------------------------------------------------------------------------
# shared expression predicates


def _contains_shape_derivation(node: ast.AST,
                               ctx: ModuleContext) -> Optional[str]:
    """The spelling of a per-call shape/width derivation inside ``node``
    (``len(...)``, ``.shape`` access), unless the derivation is wrapped
    in a pad/bucket-contract call.  Returns the offending spelling or
    None."""
    padded: Set[int] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            fname = ""
            if isinstance(n.func, ast.Name):
                fname = n.func.id
            elif isinstance(n.func, ast.Attribute):
                fname = n.func.attr
            if _PAD_CONTRACT_RE.search(fname):
                for sub in ast.walk(n):
                    padded.add(id(sub))
    for n in ast.walk(node):
        if id(n) in padded:
            continue
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                and n.func.id in _SHAPE_FNS:
            return f"{n.func.id}(...)"
        if isinstance(n, ast.Attribute) and n.attr == "shape":
            return ".shape"
    return None


def _expr_root(node: ast.AST) -> Optional[str]:
    cur = node
    while isinstance(cur, (ast.Attribute, ast.Subscript, ast.Call)):
        cur = cur.func if isinstance(cur, ast.Call) else cur.value
    return cur.id if isinstance(cur, ast.Name) else None


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


# ---------------------------------------------------------------------------
# SIM301 — recompile hazard


class RecompileHazardRule(JitRule):
    """A jit program recompiles whenever a static argument takes a new
    value or an operand takes a new shape.  The platform's contract is
    that widths are PADDED/BUCKETED into a bounded class set (pad_state,
    pow2 shape classes) before they reach a jit boundary — a raw
    ``len(...)``/``.shape`` feeding a static arg or an operand
    constructor mints one compilation per distinct value (20-40 s each
    on accelerator boxes), and a traced closure over a loop-varying
    Python value silently bakes iteration-N state into the compiled
    program (or retraces on every flip when used as a hashable
    static)."""

    id = "SIM301"
    severity = "error"
    short = ("recompile hazard: unbucketed shape feeding a jit boundary "
             "or traced closure over a varying value")

    def run(self, pkg: JitPackage) -> List[Finding]:
        out: List[Finding] = []
        for rel, mj in sorted(pkg.modules.items()):
            out.extend(self._check_call_sites(rel, mj))
            out.extend(self._check_closures(rel, mj))
        return out

    def _check_call_sites(self, rel: str, mj: ModuleJits) -> List[Finding]:
        out: List[Finding] = []
        for prog, call, _fn, _kind in mj.call_sites:
            spec = prog.spec
            # static args fed from shape-deriving expressions
            for i, arg in enumerate(call.args):
                if isinstance(arg, ast.Starred):
                    continue
                is_static = i in spec.static_argnums
                sd = _contains_shape_derivation(arg, mj.ctx)
                if is_static and sd:
                    out.append(self.finding(
                        rel, arg,
                        f"static arg {i} of jit program `{prog.name}` is "
                        f"fed from `{sd}` — one compilation per distinct "
                        "value; bucket/pad the width first (the pad_state "
                        "contract) or make it a traced operand"))
                elif sd and self._is_width_ctor(arg):
                    out.append(self.finding(
                        rel, arg,
                        f"operand {i} of jit program `{prog.name}` is "
                        f"constructed with a per-call `{sd}` width — one "
                        "compilation per distinct shape; pad to the "
                        "bucketed class set first"))
            for kw in call.keywords:
                if kw.arg is None:
                    continue
                sd = _contains_shape_derivation(kw.value, mj.ctx)
                if kw.arg in spec.static_argnames and sd:
                    out.append(self.finding(
                        rel, kw.value,
                        f"static argname `{kw.arg}` of jit program "
                        f"`{prog.name}` is fed from `{sd}` — one "
                        "compilation per distinct value; bucket/pad the "
                        "width first or make it a traced operand"))
        return out

    @staticmethod
    def _is_width_ctor(arg: ast.AST) -> bool:
        """``jnp.zeros(len(x))``-shaped operand expressions."""
        for n in ast.walk(arg):
            if isinstance(n, ast.Call) and \
                    isinstance(n.func, ast.Attribute) and \
                    n.func.attr in _WIDTH_CTORS:
                return True
        return False

    def _check_closures(self, rel: str, mj: ModuleJits) -> List[Finding]:
        """A traced function reading a free variable that its enclosing
        scope rebinds per iteration (loop body / AugAssign) — the value
        is baked at trace time and silently stale afterwards."""
        out: List[Finding] = []
        for traced, prog in mj.traced:
            encl = mj.ctx.enclosing_function(traced)
            if encl is None:
                # module-level traced fn: globals mutated via `global X`
                mutated = {g for n in mj.ctx.walk(ast.Global)
                           for g in n.names}
                if not mutated:
                    continue
                free = self._free_reads(traced)
                for name in sorted(free & mutated):
                    out.append(self.finding(
                        rel, traced,
                        f"jit-traced `{prog.name}` closes over global "
                        f"`{name}` which is mutated via `global` — the "
                        "traced value is frozen at compile time; pass it "
                        "as an operand"))
                continue
            varying = self._loop_varying(encl, traced)
            if not varying:
                continue
            free = self._free_reads(traced)
            for name in sorted(free & varying):
                out.append(self.finding(
                    rel, traced,
                    f"jit-traced `{prog.name}` closes over `{name}`, "
                    f"which `{encl.name}` rebinds per iteration — each "
                    "trace bakes one iteration's value (stale or "
                    "retraced per flip); pass it as an operand or make "
                    "the factory take it as a parameter"))
        return out

    @staticmethod
    def _free_reads(fn: ast.AST) -> Set[str]:
        local = {a.arg for a in fn.args.args + fn.args.kwonlyargs +
                 fn.args.posonlyargs}
        if fn.args.vararg:
            local.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            local.add(fn.args.kwarg.arg)
        reads: Set[str] = set()
        for n in ast.walk(fn):
            if isinstance(n, ast.Name):
                if isinstance(n.ctx, ast.Store):
                    local.add(n.id)
                else:
                    reads.add(n.id)
        return reads - local

    @staticmethod
    def _loop_varying(encl: ast.AST, traced: ast.AST) -> Set[str]:
        """Names the enclosing function rebinds inside a loop body or
        via AugAssign — per-iteration-varying values."""
        varying: Set[str] = set()
        for n in ast.walk(encl):
            if isinstance(n, ast.AugAssign) and \
                    isinstance(n.target, ast.Name):
                varying.add(n.target.id)
            elif isinstance(n, (ast.For, ast.While)):
                if any(sub is traced for sub in ast.walk(n)):
                    continue   # the traced def itself lives in the loop
                for sub in ast.walk(n):
                    if isinstance(sub, ast.Name) and \
                            isinstance(sub.ctx, ast.Store):
                        varying.add(sub.id)
                if isinstance(n, ast.For):
                    for sub in ast.walk(n.target):
                        if isinstance(sub, ast.Name):
                            varying.add(sub.id)
        return varying


# ---------------------------------------------------------------------------
# SIM302 — implicit host<->device sync in the dispatch window


class HiddenSyncRule(JitRule):
    """The PR-1 pipelined dispatch computes the kernel BEHIND the
    round's host work; the overlap survives only while nothing touches
    the in-flight result.  ``.item()``, ``float()/int()/bool()`` on a
    device value, ``np.asarray`` of a live jit result, and branching on
    a traced value each force a blocking device sync exactly where the
    launch was supposed to overlap — silently serializing the pipeline.
    Tracking is per-function: a name assigned from a jit-program call or
    a ``jnp.*`` op is a device value; the deliberate collect point reads
    from the in-flight slot (an attribute), which this rule never
    tracks, so designed syncs stay quiet."""

    id = "SIM302"
    severity = "error"
    short = ("implicit host<->device sync on a live device value inside "
             "the dispatch window")

    def run(self, pkg: JitPackage) -> List[Finding]:
        out: List[Finding] = []
        for rel, mj in sorted(pkg.modules.items()):
            fns = list(mj.ctx.walk(ast.FunctionDef, ast.AsyncFunctionDef))
            for fn in fns:
                out.extend(self._check_function(rel, mj, fn))
        return out

    def _device_names(self, mj: ModuleJits, fn: ast.AST) -> Dict[str, int]:
        """Names holding device values in ``fn`` mapped to the first
        line where they become one: jit-call results, jnp-op results,
        and direct derivations of either.  The line matters — code ABOVE
        the device assignment (the uniform_jnp host-dispatch idiom:
        ``np.asarray(counter)`` before ``counter = jnp.asarray(...)``)
        is host-side and must stay quiet."""
        ctx = mj.ctx
        tracked: Dict[str, int] = {}
        jit_calls = {id(call) for prog, call, _fn, _kind in mj.call_sites}

        def produces_device(value: ast.AST) -> bool:
            if isinstance(value, ast.Call):
                if id(value) in jit_calls:
                    return True
                r = ctx.resolve(value.func)
                if r is not None and (
                        r[0].startswith("jax.numpy.") or
                        r[0] == "jax.device_put"):
                    return True
            if isinstance(value, (ast.Subscript, ast.Attribute)):
                root = _expr_root(value)
                return root in tracked
            if isinstance(value, ast.Name):
                return value.id in tracked
            if isinstance(value, ast.Tuple):
                return any(produces_device(e) for e in value.elts)
            return False

        def bound_names(t: ast.AST) -> Set[str]:
            # only plain-name bindings: `self.x = ...` persists past the
            # function (per-function tracking can't follow it) and a
            # subscript target's index names are not bindings at all
            if isinstance(t, ast.Name):
                return {t.id}
            if isinstance(t, (ast.Tuple, ast.List)):
                out: Set[str] = set()
                for e in t.elts:
                    out |= bound_names(e)
                return out
            if isinstance(t, ast.Starred):
                return bound_names(t.value)
            return set()

        # two passes so `a = step(s); b = a[0]` settles
        for _ in range(2):
            for n in self._own_walk(fn):
                if isinstance(n, ast.Assign) and produces_device(n.value):
                    for t in n.targets:
                        for name in bound_names(t):
                            prev = tracked.get(name, n.lineno)
                            tracked[name] = min(prev, n.lineno)
        return tracked

    @staticmethod
    def _own_walk(fn: ast.AST):
        """Walk ``fn`` skipping nested def subtrees — each function is
        checked exactly once (nested defs get their own pass)."""
        skip: Set[int] = set()
        for n in ast.walk(fn):
            if n is not fn and isinstance(n, (ast.FunctionDef,
                                              ast.AsyncFunctionDef)):
                for sub in ast.walk(n):
                    skip.add(id(sub))
        for n in ast.walk(fn):
            if id(n) not in skip:
                yield n

    def _check_function(self, rel: str, mj: ModuleJits,
                        fn: ast.AST) -> List[Finding]:
        tracked = self._device_names(mj, fn)
        if not tracked:
            return []
        ctx = mj.ctx
        out: List[Finding] = []
        # an EXPLICIT `jax.block_until_ready(...)` names the sync point;
        # pulls after it are reads of settled buffers, not implicit syncs
        blocked_at: Optional[int] = None
        for n in self._own_walk(fn):
            if isinstance(n, ast.Call):
                r = ctx.resolve(n.func)
                if r is not None and r[0] == "jax.block_until_ready":
                    if blocked_at is None or n.lineno < blocked_at:
                        blocked_at = n.lineno

        def live(node: ast.AST, name: Optional[str]) -> bool:
            line = getattr(node, "lineno", 0)
            if blocked_at is not None and line >= blocked_at:
                return False
            return name in tracked and line >= tracked[name]

        for n in self._own_walk(fn):
            if isinstance(n, ast.Call):
                f = n.func
                # x.item()
                if isinstance(f, ast.Attribute) and f.attr == "item" \
                        and live(n, _expr_root(f.value)):
                    out.append(self.finding(
                        rel, n,
                        f"`.item()` on device value "
                        f"`{_expr_root(f.value)}` blocks until the "
                        "in-flight kernel finishes — an implicit sync "
                        "inside the dispatch window; collect first, then "
                        "read host-side"))
                # float(x) / int(x) / bool(x)
                elif isinstance(f, ast.Name) and \
                        f.id in _SYNC_COERCIONS and n.args and \
                        live(n, _expr_root(n.args[0])):
                    out.append(self.finding(
                        rel, n,
                        f"`{f.id}()` of device value "
                        f"`{_expr_root(n.args[0])}` is an implicit "
                        "host sync — it serializes the pipelined "
                        "dispatch; keep the value on device or collect "
                        "explicitly"))
                else:
                    r = ctx.resolve(f)
                    if r is not None and r[0] in _NP_PULLS and n.args and \
                            live(n, _expr_root(n.args[0])):
                        out.append(self.finding(
                            rel, n,
                            f"`{r[1]}.{r[0].rsplit('.', 1)[1]}` of live "
                            f"jit result "
                            f"`{_expr_root(n.args[0])}` pulls the buffer "
                            "to the host mid-window — if this is the "
                            "designed collect point, say so with a "
                            "pragma"))
            elif isinstance(n, (ast.If, ast.While)):
                test = n.test
                if (blocked_at is None or
                        getattr(test, "lineno", 0) < blocked_at) and \
                        self._branches_on_device(test, tracked):
                    out.append(self.finding(
                        rel, test,
                        f"branching on device value "
                        f"`{sorted(_names_in(test) & set(tracked))[0]}` "
                        "forces "
                        "a blocking sync (traced-value branch) — compute "
                        "the predicate host-side or use lax.cond in the "
                        "kernel"))
        return out

    @staticmethod
    def _branches_on_device(test: ast.AST, tracked: Dict[str, int]) -> bool:
        line = getattr(test, "lineno", 0)
        if not any(line >= tracked[nm]
                   for nm in sorted(_names_in(test) & set(tracked))):
            return False
        # identity tests against None are shape-free host checks
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return False
        # len()/.shape/isinstance predicates read metadata (or the host
        # type), not the buffer: exempt names that only appear there
        shallow: Set[int] = set()
        for n in ast.walk(test):
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                    and n.func.id in ("len", "isinstance", "getattr",
                                      "hasattr"):
                shallow.update(id(s) for s in ast.walk(n))
            elif isinstance(n, ast.Attribute) and n.attr in ("shape",
                                                            "ndim",
                                                            "dtype"):
                shallow.update(id(s) for s in ast.walk(n))
        for n in ast.walk(test):
            if isinstance(n, ast.Name) and n.id in tracked \
                    and getattr(n, "lineno", 0) >= tracked[n.id] \
                    and id(n) not in shallow:
                return True
        return False


# ---------------------------------------------------------------------------
# SIM303 — dtype-promotion drift in kernel-tagged files


class PromotionDriftRule(JitRule):
    """The kernel plane's contract is non-negative int64 arithmetic —
    what makes ``py // == C / == numpy int64`` exact (the logic-IR
    foundation).  A Python float literal or true division touching a
    sim-time lane weak-type-promotes the whole expression to float —
    ns timestamps silently lose integer exactness above 2**53 and the
    three planes drift.  This extends SIM204's carrier tracking from
    casts to ARITHMETIC, scoped to kernel-tagged files
    ([tool.simjit] kernel globs)."""

    id = "SIM303"
    severity = "error"
    short = ("float promotion on a sim-time lane in a kernel-tagged "
             "file (int64 contract)")

    def run(self, pkg: JitPackage) -> List[Finding]:
        out: List[Finding] = []
        for rel, mj in sorted(pkg.modules.items()):
            if not pkg.is_kernel(rel):
                continue
            out.extend(self._check_module(rel, mj))
        return out

    def _timey_in(self, node: ast.AST) -> Optional[str]:
        for n in ast.walk(node):
            nm = None
            if isinstance(n, ast.Name):
                nm = n.id
            elif isinstance(n, ast.Attribute):
                nm = n.attr
            if nm and _is_timey(nm):
                return nm
        return None

    def _check_module(self, rel: str, mj: ModuleJits) -> List[Finding]:
        out: List[Finding] = []
        for node in mj.ctx.walk(ast.BinOp):
            if isinstance(node.op, ast.Div):
                nm = self._timey_in(node.left) or self._timey_in(node.right)
                if nm:
                    out.append(self.finding(
                        rel, node,
                        f"true division on sim-time lane `{nm}` promotes "
                        "the int64 ns value to float — use `//` (the "
                        "non-negative int64 contract keeps all three "
                        "planes bit-exact)"))
                continue
            if isinstance(node.op, (ast.Mult, ast.Add, ast.Sub)):
                for side, other in ((node.left, node.right),
                                    (node.right, node.left)):
                    if isinstance(side, ast.Constant) and \
                            isinstance(side.value, float):
                        nm = self._timey_in(other)
                        if nm:
                            out.append(self.finding(
                                rel, node,
                                f"float literal {side.value!r} in "
                                f"arithmetic with sim-time lane `{nm}` "
                                "weak-type-promotes the int64 ns value "
                                "to float — spell the coefficient as an "
                                "integer ratio (num // den)"))
                        break
        for node in mj.ctx.walk(ast.Call):
            f = node.func
            # x.astype(float32) / jnp.float32(x) on a timey expression
            if isinstance(f, ast.Attribute) and f.attr == "astype" \
                    and node.args and self._float_dtype(node.args[0]):
                nm = self._timey_in(f.value)
                if nm:
                    out.append(self.finding(
                        rel, node,
                        f"sim-time lane `{nm}` cast to "
                        f"{self._float_dtype(node.args[0])} — ns "
                        "timestamps lose integer exactness above 2**53; "
                        "keep the lane int64"))
            elif isinstance(f, ast.Attribute) and \
                    f.attr in _FLOAT_DTYPES and node.args:
                nm = self._timey_in(node.args[0])
                if nm:
                    out.append(self.finding(
                        rel, node,
                        f"sim-time lane `{nm}` cast to {f.attr} — ns "
                        "timestamps lose integer exactness above 2**53; "
                        "keep the lane int64"))
        return out

    @staticmethod
    def _float_dtype(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Attribute) and node.attr in _FLOAT_DTYPES:
            return node.attr
        if isinstance(node, ast.Name) and node.id in _FLOAT_DTYPES:
            return node.id
        if isinstance(node, ast.Constant) and \
                isinstance(node.value, str) and node.value in _FLOAT_DTYPES:
            return node.value
        return None


# ---------------------------------------------------------------------------
# SIM304 — donation misuse


class DonationMisuseRule(JitRule):
    """``donate_argnums`` hands the operand buffers to XLA.  Two call
    sites sharing ONE donated program means two owners of the same
    aliasing contract — the second caller's pre-donation reads race the
    first caller's invalidated buffers the moment the call order
    changes (SIM004 sees each site in isolation; this rule sees the
    pair).  And donation pinned to the CPU backend is the PR-1 trap:
    a donated PJRT-CPU call executes SYNCHRONOUSLY and still copies
    (measured 114 ms vs 0.33 ms undonated), destroying the pipeline
    it was meant to feed — the backend-gated non-donating twin
    (step_window_flush_for_backend) exists precisely for this."""

    id = "SIM304"
    severity = "error"
    short = ("donated jit shared by two call-site owners, or donation "
             "pinned to the CPU backend")

    def run(self, pkg: JitPackage) -> List[Finding]:
        out: List[Finding] = []
        # (b) donation + backend="cpu" at the creation site
        for rel, mj in sorted(pkg.modules.items()):
            for name, prog in sorted(mj.programs.items()):
                if prog.spec.donate_argnums and prog.spec.backend == "cpu":
                    anchor = ast.Module(body=[], type_ignores=[])
                    anchor.lineno, anchor.col_offset = prog.line, 0
                    out.append(self.finding(
                        rel, anchor,
                        f"jit program `{name}` donates buffers on the "
                        "CPU backend — donated PJRT-CPU calls execute "
                        "synchronously AND copy (the PR-1 trap); use a "
                        "non-donating variant on cpu "
                        "(step_window_flush_for_backend pattern)"))
        # (a) one donated program, two call-site owners (package-wide:
        # call sites of imported names resolve by trailing symbol)
        owners: Dict[int, Set[Tuple[str, str]]] = {}
        sites: Dict[int, List[Tuple[str, ast.Call]]] = {}
        progs: Dict[int, JitProgram] = {}
        for rel, mj in sorted(pkg.modules.items()):
            for prog, call, fn, kind in mj.call_sites:
                # handle dispatch (self._step(...)) has one owner object
                # by construction; only direct-name sharing pairs alias
                if kind != "name" or not prog.spec.donate_argnums:
                    continue
                progs[id(prog)] = prog
                owners.setdefault(id(prog), set()).add((rel, fn or "<module>"))
                sites.setdefault(id(prog), []).append((rel, call))
            # imported donated programs called by bare name
            for call in mj.ctx.walk(ast.Call):
                if not isinstance(call.func, ast.Name):
                    continue
                cands = pkg.by_symbol.get(call.func.id, ())
                for cand in cands:
                    if cand.relpath == rel or not cand.spec.donate_argnums:
                        continue
                    r = mj.ctx.aliases.get(call.func.id)
                    if r is None or not r.endswith(call.func.id):
                        continue
                    fn2 = mj.ctx.enclosing_function(call)
                    progs[id(cand)] = cand
                    owners.setdefault(id(cand), set()).add(
                        (rel, fn2.name if fn2 else "<module>"))
                    sites.setdefault(id(cand), []).append((rel, call))
        for pid, own in sorted(owners.items(),
                               key=lambda kv: progs[kv[0]].name):
            if len(own) < 2:
                continue
            prog = progs[pid]
            names = ", ".join(f"{r}:{f}" for r, f in sorted(own))
            for rel, call in sorted(sites[pid],
                                    key=lambda s: (s[0], s[1].lineno)):
                out.append(self.finding(
                    rel, call,
                    f"donated jit program `{prog.name}` is called from "
                    f"multiple owners ({names}) — two callers of one "
                    "donation contract alias each other's invalidated "
                    "buffers; give each owner its own jit (or route "
                    "through one owner)"))
        return out


# ---------------------------------------------------------------------------
# SIM305 — compile-budget audit


class CompileBudgetRule(JitRule):
    """The checked-in ``[tool.simjit.budget]`` table declares, per
    module, how many jit program identities the module may mint; this
    rule statically enumerates the actual surface and fails on ANY
    drift — a new jit site without a conscious budget bump (a code path
    adding unbounded cache keys fails lint instead of churning
    ``fleet.compiles`` at 2 a.m. on a TPU box), AND a stale over-
    declared entry after a surface shrinks.  Unbounded in-function jit
    creation is always a finding.  The runtime halves of the same table
    (dotted keys: ``fleet.compiles``, ``device_plane.sharded_variants``)
    are cross-checked by ``simfleet smoke``; here the sharded-variant
    literal cap must match its declared budget."""

    id = "SIM305"
    severity = "error"
    short = ("compile-key count drifted from the checked-in "
             "[tool.simjit.budget] table")

    def run(self, pkg: JitPackage) -> List[Finding]:
        out: List[Finding] = []
        module_budget = {k: v for k, v in pkg.budget.items()
                         if k.endswith(".py")}
        counted: Dict[str, int] = {}
        for rel, mj in sorted(pkg.modules.items()):
            count, problems = pkg.static_key_count(rel)
            for prog, msg in problems:
                anchor = ast.Module(body=[], type_ignores=[])
                anchor.lineno, anchor.col_offset = prog.line, 0
                out.append(self.finding(rel, anchor, msg))
            if count:
                counted[rel] = count
        for rel, count in sorted(counted.items()):
            declared = module_budget.get(rel)
            anchor = ast.Module(body=[], type_ignores=[])
            anchor.lineno, anchor.col_offset = 1, 0
            if declared is None:
                out.append(self.finding(
                    rel, anchor,
                    f"module mints {count} jit compile key(s) but has no "
                    "[tool.simjit.budget] entry — declare the budget in "
                    "pyproject.toml so growth is a conscious decision"))
            elif declared != count:
                direction = "grew past" if count > declared else \
                    "shrank below"
                out.append(self.finding(
                    rel, anchor,
                    f"compile surface {direction} its budget: "
                    f"{count} enumerated key(s) vs "
                    f"[tool.simjit.budget] = {declared} — "
                    "update the table to match the surface"))
        for rel in sorted(set(module_budget) - set(counted)):
            # a budgeted module OUTSIDE this run's analysis subset (a
            # single-file invocation) is unknowable, not stale — only an
            # analyzed module minting zero keys, or one gone from the
            # tree entirely, means the entry went stale
            if rel not in pkg.modules and \
                    os.path.isfile(os.path.join(pkg.config.root, rel)):
                continue
            anchor = ast.Module(body=[], type_ignores=[])
            anchor.lineno, anchor.col_offset = 1, 0
            out.append(self.finding(
                "pyproject.toml", anchor,
                f"[tool.simjit.budget] entry `{rel}` = "
                f"{module_budget[rel]} is stale — the module mints no "
                "enumerable jit compile keys (removed surface? drop the "
                "entry)"))
        # literal variant-cache caps must match their declared runtime
        # budget (the static half of the fleet-smoke cross-check)
        for key, declared in sorted(pkg.budget.items()):
            if not key.endswith(".sharded_variants"):
                continue
            for rel, mj in sorted(pkg.modules.items()):
                if not rel.endswith("device_plane.py"):
                    continue
                for prog in mj.programs.values():
                    if prog.cache_cap is not None and \
                            prog.cache_cap != declared:
                        anchor = ast.Module(body=[], type_ignores=[])
                        anchor.lineno, anchor.col_offset = prog.line, 0
                        out.append(self.finding(
                            rel, anchor,
                            f"variant-cache literal cap "
                            f"{prog.cache_cap} != [tool.simjit.budget] "
                            f"`{key}` = {declared} — the checked-in "
                            "budget and the code bound must agree"))
        return out


CATALOG: List[JitRule] = [
    RecompileHazardRule(),
    HiddenSyncRule(),
    PromotionDriftRule(),
    DonationMisuseRule(),
    CompileBudgetRule(),
]
