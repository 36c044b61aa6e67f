"""Pass 1 — jit-cache stability lint.

Walks every function reachable from a ``jax.jit`` / ``pl.pallas_call``
root (the *traced scope*) and flags retrace / stale-cache hazards:

- ``env-read-in-jit`` — ``os.environ`` / ``os.getenv`` read lexically
  inside traced scope, or a call into a function that (transitively)
  reads env without the resolver guard.  An env value read at trace time
  is baked into the compiled executable but is not part of the jit cache
  key: flipping the knob later silently serves the stale trace.
- ``env-resolver-default-in-jit`` — traced code calling a recognized
  *env resolver* (``env_fused_select``-style: ``if p is not None:
  return p`` dominating the env read) without passing the knob
  explicitly.  Explicitly-threaded knobs are the repo's contract for
  "resolved outside jit"; the default path is the hazard.
- ``config-attr-in-jit`` — reads of ``config.*`` / ``cfg.*`` /
  ``IndexConfig``-annotated parameters inside traced scope (config
  attributes are plain Python values: baked, not keyed).
- ``static-argname-unknown`` — ``static_argnames`` naming a parameter
  the decorated function does not have (typo ⇒ the knob silently stays
  traced or jax errors at first call).
- ``traced-operand-as-static`` — ``static_argnames`` naming a declared
  traced-operand (the PR 6 mask rule: liveness masks and data arrays
  must be traced operands, never cache keys — a mask as a key retraces
  on every tombstone flip).
- ``lru-jit-env`` — an ``lru_cache``'d factory that builds a jit
  closure while (transitively) reading env: the env value lands in the
  cached closure but not in the lru key.
- ``lru-jit-unkeyed-binding`` — a ``partial`` binding inside an
  ``lru_cache``'d jit factory whose value is neither a parameter of the
  factory (⊆ the cache key) nor a module-level constant: the closure
  captures state the key does not cover.
- ``jit-in-local-scope`` (report) — ``@jax.jit`` on a def nested inside
  a function: each outer call builds a fresh jit cache (full retrace)
  unless the closure is deliberately reused.

The pass also returns audit metadata (env readers, resolvers, traced
roots/population) so the report *proves* every REPRO_* read resolves
outside jit rather than merely not flagging it.
"""
from __future__ import annotations

import ast
import dataclasses

from repro.lint.findings import Finding, SEVERITY_REPORT

# Names that must always be traced operands, never static/jit-key values
# (PR 6: the per-row liveness mask is traced so tombstone flips and base
# swaps never retrace; data/query arrays likewise).
TRACED_OPERAND_NAMES = frozenset(
    {"active", "mask", "codes", "queries", "x", "w", "split"})

# Wrappers whose first positional argument is the function that actually
# gets traced — unwrapped when resolving jit(...) / pallas_call(...) roots.
_UNWRAP = {"partial", "shard_map", "vmap", "checkpoint",
           "remat"}

_CONFIG_NAMES = {"config", "cfg"}


@dataclasses.dataclass
class FunctionInfo:
    node: ast.AST                  # FunctionDef / AsyncFunctionDef
    src: object                    # SourceModule
    qualname: str
    params: list
    parent: object = None          # enclosing FunctionInfo or None
    class_name: str = ""
    nested: dict = dataclasses.field(default_factory=dict)
    local_imports: dict = dataclasses.field(default_factory=dict)
    config_params: set = dataclasses.field(default_factory=set)
    env_reads: list = dataclasses.field(default_factory=list)  # ast nodes
    resolver_param: str = ""       # guard param name if resolver idiom
    calls: list = dataclasses.field(default_factory=list)      # ast.Call
    tainted: bool = False
    traced: bool = False
    traced_via: str = ""

    @property
    def key(self):
        return (self.src.module, self.qualname)


def _name_of(node):
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def _is_jit_expr(node) -> bool:
    """``jit`` / ``jax.jit`` as an expression."""
    return _name_of(node) == "jit"


def _unwrap_traced_arg(node):
    """Peel partial/shard_map/vmap wrappers down to the traced callee."""
    while isinstance(node, ast.Call) and _name_of(node.func) in _UNWRAP:
        if not node.args:
            return None
        node = node.args[0]
    return node if isinstance(node, ast.Name) else None


def _static_argnames(call: ast.Call):
    """(names, node) from a jit/partial(jit) call's static_argnames kwarg."""
    for kw in call.keywords:
        if kw.arg != "static_argnames":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            return [v.value], kw.value
        if isinstance(v, (ast.Tuple, ast.List)):
            names = [e.value for e in v.elts
                     if isinstance(e, ast.Constant) and isinstance(e.value, str)]
            return names, kw.value
    return [], None


def _jit_decoration(dec):
    """If ``dec`` marks the function as jitted, return the jit Call node
    (for static_argnames extraction) or True."""
    if _is_jit_expr(dec):
        return True
    if isinstance(dec, ast.Call):
        if _is_jit_expr(dec.func):
            return dec
        if _name_of(dec.func) == "partial" and dec.args and \
                _is_jit_expr(dec.args[0]):
            return dec
    return None


def _is_lru_decoration(dec) -> bool:
    if _name_of(dec) == "lru_cache":
        return True
    return isinstance(dec, ast.Call) and _name_of(dec.func) == "lru_cache"


class _Index:
    """Function/import/constant tables over all scanned modules."""

    def __init__(self, modules):
        self.modules = {m.module: m for m in modules}
        self.functions = {}        # (module, qualname) -> FunctionInfo
        self.toplevel = {}         # (module, name) -> FunctionInfo
        self.imports = {}          # module -> {local: ("module"|"symbol", ...)}
        self.constants = {}        # module -> set of single-assignment names
        for m in modules:
            self._index_module(m)

    def _index_module(self, src):
        imports = {}
        consts = {}
        for stmt in src.tree.body:
            if isinstance(stmt, ast.Import):
                for a in stmt.names:
                    local = a.asname or a.name.split(".")[0]
                    imports[local] = ("module", a.name)
            elif isinstance(stmt, ast.ImportFrom) and stmt.module \
                    and stmt.level == 0:
                for a in stmt.names:
                    imports[a.asname or a.name] = \
                        ("symbol", stmt.module, a.name)
            for t in _binding_names(stmt):
                consts[t] = consts.get(t, 0) + 1
        self.imports[src.module] = imports
        self.constants[src.module] = {n for n, c in consts.items() if c == 1}
        self._index_scope(src, src.tree.body, parent=None, prefix="",
                          class_name="")

    def _index_scope(self, src, body, parent, prefix, class_name):
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._index_function(src, stmt, parent, prefix, class_name)
            elif isinstance(stmt, ast.ClassDef):
                self._index_scope(src, stmt.body, parent,
                                  prefix + stmt.name + ".", stmt.name)

    def _index_function(self, src, node, parent, prefix, class_name):
        qualname = prefix + node.name
        a = node.args
        params = [p.arg for p in
                  a.posonlyargs + a.args + a.kwonlyargs]
        info = FunctionInfo(node=node, src=src, qualname=qualname,
                            params=params, parent=parent,
                            class_name=class_name)
        for p in a.posonlyargs + a.args + a.kwonlyargs:
            ann = p.annotation
            if ann is not None and _name_of(ann) == "IndexConfig":
                info.config_params.add(p.arg)
        self.functions[info.key] = info
        if parent is None and not class_name:
            self.toplevel[(src.module, node.name)] = info
        if parent is not None:
            parent.nested[node.name] = info
        self._scan_body(info)
        self._index_scope(src, node.body, parent=info,
                          prefix=qualname + ".", class_name="")

    def _scan_body(self, info):
        """Collect env reads and calls lexically in this function's body
        (nested defs are their own FunctionInfo)."""
        imports = self.imports[info.src.module]

        def local_env_name(name):
            tgt = imports.get(name)
            return tgt and tgt[0] == "symbol" and tgt[1] == "os" \
                and tgt[2] in ("environ", "getenv")

        for node in _walk_shallow(info.node):
            if isinstance(node, ast.Attribute):
                base = node.value
                if isinstance(base, ast.Name):
                    tgt = imports.get(base.id)
                    if tgt == ("module", "os") and \
                            node.attr in ("environ", "getenv"):
                        info.env_reads.append(node)
            elif isinstance(node, ast.Name) and local_env_name(node.id):
                info.env_reads.append(node)
            elif isinstance(node, ast.Call):
                info.calls.append(node)
            elif isinstance(node, ast.Import):
                for a in node.names:
                    info.local_imports[a.asname or a.name.split(".")[0]] = \
                        ("module", a.name)
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for a in node.names:
                    info.local_imports[a.asname or a.name] = \
                        ("symbol", node.module, a.name)
        if info.env_reads:
            info.resolver_param = _resolver_guard(info)

    # -- call resolution -----------------------------------------------------

    def resolve_call(self, info, call):
        """Best-effort FunctionInfo for a call's callee, else None."""
        func = call.func
        if isinstance(func, ast.Name):
            scope = info
            while scope is not None:
                if func.id in scope.nested:
                    return scope.nested[func.id]
                scope = scope.parent
            hit = self.toplevel.get((info.src.module, func.id))
            if hit:
                return hit
            tgt = self.imports[info.src.module].get(func.id)
            if tgt and tgt[0] == "symbol":
                return self.toplevel.get((tgt[1], tgt[2]))
            return None
        if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
            base, attr = func.value.id, func.attr
            if base == "self" and info.class_name:
                return self.functions.get(
                    (info.src.module, f"{info.class_name}.{attr}"))
            tgt = None
            scope = info
            while scope is not None and tgt is None:
                tgt = scope.local_imports.get(base)
                scope = scope.parent
            tgt = tgt or self.imports[info.src.module].get(base)
            if tgt:
                if tgt[0] == "module":
                    return self.toplevel.get((tgt[1], attr))
                mod = f"{tgt[1]}.{tgt[2]}"      # from pkg import submodule
                if mod in self.modules:
                    return self.toplevel.get((mod, attr))
        return None


def _binding_names(stmt):
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
        return [stmt.name]
    if isinstance(stmt, ast.Import):
        return [a.asname or a.name.split(".")[0] for a in stmt.names]
    if isinstance(stmt, ast.ImportFrom):
        return [a.asname or a.name for a in stmt.names]
    if isinstance(stmt, ast.Assign):
        return [t.id for t in stmt.targets if isinstance(t, ast.Name)]
    if isinstance(stmt, (ast.AnnAssign, ast.AugAssign)) and \
            isinstance(stmt.target, ast.Name):
        return [stmt.target.id]
    return []


def _walk_shallow(func_node):
    """ast.walk over a function body, not descending into nested defs."""
    stack = list(func_node.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(node.decorator_list)   # decorators still run here
            continue
        stack.extend(ast.iter_child_nodes(node))


def _resolver_guard(info) -> str:
    """Return the guard parameter name if the function follows the env
    resolver idiom: ``if p is not None: ... return p`` at top level of the
    body, *before* any env read (so explicitly-passed knobs never hit env).
    """
    first_env_line = min(n.lineno for n in info.env_reads)
    for stmt in info.node.body:
        if stmt.lineno >= first_env_line:
            break
        if not isinstance(stmt, ast.If):
            continue
        t = stmt.test
        if not (isinstance(t, ast.Compare) and isinstance(t.left, ast.Name)
                and len(t.ops) == 1 and isinstance(t.ops[0], ast.IsNot)
                and isinstance(t.comparators[0], ast.Constant)
                and t.comparators[0].value is None):
            continue
        p = t.left.id
        if p not in info.params:
            continue
        last = stmt.body[-1]
        if isinstance(last, ast.Return) and isinstance(last.value, ast.Name) \
                and last.value.id == p:
            return p
    return ""


def _call_passes_guard(info, call, target) -> bool:
    """Does this call site pass the resolver's guard parameter explicitly?"""
    p = target.resolver_param
    if any(kw.arg == p for kw in call.keywords):
        return True
    try:
        pos = target.params.index(p)
    except ValueError:
        return False
    # method calls through self shift positionals by one
    shift = 1 if target.params[:1] == ["self"] else 0
    return len(call.args) > pos - shift


def run(modules, package_prefix="repro") -> tuple[list, dict]:
    """Run the pass over SourceModules; returns (findings, audit_meta)."""
    idx = _Index(modules)
    findings = []

    # ---- taint fixpoint: may a call into F read env un-neutralized? ----
    infos = list(idx.functions.values())
    changed = True
    while changed:
        changed = False
        for f in infos:
            if f.tainted:
                continue
            t = bool(f.env_reads) and not f.resolver_param
            if not t:
                for call in f.calls:
                    tgt = idx.resolve_call(f, call)
                    if tgt is None:
                        continue
                    if tgt.resolver_param:
                        if not _call_passes_guard(f, call, tgt):
                            t = True
                            break
                    elif tgt.tainted:
                        t = True
                        break
            if t:
                f.tainted = True
                changed = True

    # ---- traced-scope roots ----
    roots = []
    for f in infos:
        for dec in f.node.decorator_list:
            jd = _jit_decoration(dec)
            if jd is not None:
                roots.append((f, f"@{f.qualname}"))
                call = jd if isinstance(jd, ast.Call) else None
                if call is not None:
                    _check_static_argnames(f, call, f, findings)
                if f.parent is not None:
                    findings.append(Finding(
                        "jit_stability", "jit-in-local-scope", f.src.rel,
                        f.qualname, line=f.node.lineno,
                        severity=SEVERITY_REPORT, key=f.qualname,
                        message=f"@jit on local def '{f.qualname}': each "
                                f"call of the enclosing function builds a "
                                f"fresh jit cache (retraces unless the "
                                f"closure is reused)"))
        # jit(...) / pallas_call(...) used as expressions
        for call in f.calls:
            fn_name = _name_of(call.func)
            if fn_name == "jit" and call.args:
                tgt_name = _unwrap_traced_arg(call.args[0])
                tgt = None
                if tgt_name is not None:
                    tgt = idx.resolve_call(
                        f, ast.Call(func=tgt_name, args=[], keywords=[]))
                if tgt is not None:
                    roots.append((tgt, f"jit() in {f.qualname}"))
                    _check_static_argnames(tgt, call, f, findings)
            elif fn_name == "pallas_call" and call.args:
                tgt_name = _unwrap_traced_arg(call.args[0])
                if tgt_name is not None:
                    tgt = idx.resolve_call(
                        f, ast.Call(func=tgt_name, args=[], keywords=[]))
                    if tgt is not None:
                        roots.append((tgt, f"pallas_call in {f.qualname}"))

    # ---- BFS the traced closure ----
    queue = []
    for f, via in roots:
        if not f.traced:
            f.traced, f.traced_via = True, via
            queue.append(f)
    while queue:
        f = queue.pop()
        for child in f.nested.values():     # closures run under the trace
            if not child.traced:
                child.traced, child.traced_via = True, f.traced_via
                queue.append(child)
        for call in f.calls:
            tgt = idx.resolve_call(f, call)
            if tgt is None or tgt.traced:
                continue
            if tgt.resolver_param:
                # resolvers are judged at the call site (guard passed →
                # knob resolved by the caller, outside the trace; guard
                # defaulted → env-resolver-default-in-jit below) — their
                # bodies are not part of the hazard surface here
                continue
            tgt.traced, tgt.traced_via = True, f.traced_via
            queue.append(tgt)

    # ---- findings inside traced scope ----
    for f in infos:
        if not f.traced:
            continue
        for node in f.env_reads:
            findings.append(Finding(
                "jit_stability", "env-read-in-jit", f.src.rel, f.qualname,
                line=node.lineno, key="direct",
                message=f"os.environ read inside traced scope "
                        f"(traced via {f.traced_via}): the value is baked "
                        f"into the trace but is not a jit cache key"))
        for call in f.calls:
            tgt = idx.resolve_call(f, call)
            if tgt is None:
                continue
            if tgt.resolver_param:
                if not _call_passes_guard(f, call, tgt):
                    findings.append(Finding(
                        "jit_stability", "env-resolver-default-in-jit",
                        f.src.rel, f.qualname, line=call.lineno,
                        key=f"call:{tgt.qualname}",
                        message=f"traced scope calls env resolver "
                                f"{tgt.qualname}() without passing "
                                f"'{tgt.resolver_param}' explicitly — the "
                                f"default path reads REPRO_* env at trace "
                                f"time"))
            elif tgt.tainted:
                findings.append(Finding(
                    "jit_stability", "env-read-in-jit", f.src.rel,
                    f.qualname, line=call.lineno, key=f"call:{tgt.qualname}",
                    message=f"traced scope calls {tgt.qualname}() which "
                            f"(transitively) reads env without the resolver "
                            f"guard"))
        for node in _walk_shallow(f.node):
            if isinstance(node, ast.Attribute) and \
                    isinstance(node.value, ast.Name) and \
                    isinstance(node.ctx, ast.Load):
                nid = node.value.id
                if nid in f.config_params or nid in _CONFIG_NAMES:
                    findings.append(Finding(
                        "jit_stability", "config-attr-in-jit", f.src.rel,
                        f.qualname, line=node.lineno,
                        key=f"{nid}.{node.attr}",
                        message=f"read of {nid}.{node.attr} inside traced "
                                f"scope: config attributes are baked into "
                                f"the trace, not jit cache keys — hoist the "
                                f"read outside or make it a static arg"))

    # ---- lru_cache'd jit factories ----
    for f in infos:
        if not any(_is_lru_decoration(d) for d in f.node.decorator_list):
            continue
        has_jit = any(_name_of(c.func) == "jit" for c in f.calls)
        if not has_jit:
            continue
        if f.tainted:
            findings.append(Finding(
                "jit_stability", "lru-jit-env", f.src.rel, f.qualname,
                line=f.node.lineno, key="env",
                message=f"lru_cache'd jit factory {f.qualname} reads env "
                        f"(transitively): the env value is captured by the "
                        f"cached closure but absent from the lru key"))
        consts = idx.constants[f.src.module]
        for call in f.calls:
            if _name_of(call.func) != "partial":
                continue
            for bound_name, value in _partial_bindings(call):
                if _binding_is_keyed(value, f.params, consts):
                    continue
                findings.append(Finding(
                    "jit_stability", "lru-jit-unkeyed-binding", f.src.rel,
                    f.qualname, line=call.lineno, key=f"bind:{bound_name}",
                    message=f"partial binding '{bound_name}' in lru_cache'd "
                            f"jit factory {f.qualname} is neither a factory "
                            f"parameter nor a module constant: the closure "
                            f"captures state the cache key does not cover"))

    meta = {
        "traced_functions": sorted(
            f"{f.src.module}.{f.qualname}" for f in infos if f.traced),
        "env_readers": sorted(
            f"{f.src.module}.{f.qualname}" for f in infos if f.env_reads),
        "env_resolvers": sorted(
            f"{f.src.module}.{f.qualname}" for f in infos
            if f.resolver_param),
        "roots": sorted({via for f, via in roots}),
    }
    return findings, meta


def _partial_bindings(call):
    out = []
    for i, a in enumerate(call.args[1:], 1):
        out.append((f"arg{i}", a))
    for kw in call.keywords:
        if kw.arg is not None:
            out.append((kw.arg, kw.value))
    return out


def _binding_is_keyed(value, params, consts) -> bool:
    if isinstance(value, ast.Constant):
        return True
    if isinstance(value, ast.Name):
        return value.id in params or value.id in consts
    if isinstance(value, ast.Attribute):        # e.g. jnp.float32
        root = value
        while isinstance(root, ast.Attribute):
            root = root.value
        return isinstance(root, ast.Name) and \
            (root.id in params or root.id in consts)
    if isinstance(value, (ast.Tuple, ast.List)):
        return all(_binding_is_keyed(e, params, consts) for e in value.elts)
    return False


def _check_static_argnames(target, jit_call, site, findings):
    names, _ = _static_argnames(jit_call)
    if not names:
        return
    for n in names:
        if target is not None and target.params and n not in target.params:
            findings.append(Finding(
                "jit_stability", "static-argname-unknown", site.src.rel,
                target.qualname, line=jit_call.lineno, key=f"name:{n}",
                message=f"static_argnames names '{n}' which is not a "
                        f"parameter of {target.qualname}"))
        if n in TRACED_OPERAND_NAMES:
            findings.append(Finding(
                "jit_stability", "traced-operand-as-static", site.src.rel,
                target.qualname if target else site.qualname,
                line=jit_call.lineno, key=f"name:{n}",
                message=f"'{n}' is a declared traced operand (PR 6 mask "
                        f"rule) but appears in static_argnames: using it as "
                        f"a jit cache key retraces on every value change"))
