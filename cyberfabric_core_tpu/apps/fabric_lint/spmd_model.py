"""Whole-program SPMD provenance model — pass 3 of the interprocedural
analyzer.

Since PR 13 lifted the continuous scheduler onto a GSPMD mesh, the dominant
new bug class is sharding/device-boundary drift: a bare upload that silently
replicates a sharded-intent array, a ``shard_map`` spec naming an axis the
mesh does not have, or a config field that shapes a compiled program but is
missing from the AOT serving-set key (the ``device_stop_width`` bug PR 7
fixed by hand). SH01 sees one function at a time; this module builds the
global picture the SH02–SH04/AK01 rules (``rules/spmd.py``) run over:

- a **mesh inventory**: every ``jax.sharding.Mesh`` / ``AbstractMesh`` /
  ``build_mesh`` construction site with its axis names, resolved through
  the helper when the site itself carries none (``build_mesh`` is looked up
  project-wide and its internal ``Mesh(..., axis_names=...)`` literal is
  inherited). The union of all literal axis tuples is the project's **axis
  universe** — the set SH03 validates ``PartitionSpec`` names against;
- a **device-value provenance lattice** — ``host`` / ``device`` /
  ``replicated`` / ``sharded(axes)`` / ``unknown`` — assigned to every
  ``self.<attr>`` of a mesh-mode class by joining the provenance of its
  assignment sites (``np.*`` ⇒ host, ``jnp.*`` ⇒ device, ``self._dev(...)``
  / ``parallel.sharding.replicated`` ⇒ replicated, ``device_put`` with a
  ``NamedSharding(mesh, P(axes))`` destination ⇒ sharded(axes)). SH02
  forward-propagates the same lattice through locals to every jitted
  dispatch call;
- a **jitted-dispatch map** per class: the ``self._X_fn = jax.jit(...)`` or
  ``... = step_programs(key)`` attributes whose call sites SH02 guards;
- a **bare-upload summary** over pass 1's call graph: for every method, a
  witness chain when some call path from it reaches a destination-less
  ``jax.device_put`` — how SH02 generalizes SH01 from syntax to dataflow
  (the helper-routed upload SH01 cannot see);
- an **AOT key model**: the ``EngineConfig`` field set, the key-tuple
  parameter names of ``aot_tpu.serving_programs``/``aot_compile``, and the
  **program-shape field set** — every config field that reaches
  ``_build_programs`` (directly, through derived attributes like
  ``self._stop_width = max(1, config.device_stop_width)``, through locals,
  or through config methods like ``resolve_lookahead_depth()``) or that flows
  into a device-array shape constructor (``jnp.zeros/full/...``,
  ``jax.random.split``) anywhere in the engine class. AK01 is the set
  difference: shape-affecting but not name-matched by any key parameter.

``--shard-graph`` dumps this model (docs/shard_graph.json); like the lock
graph, the emitters exclude line numbers so the drift check churns on
structure, not on unrelated edits.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Iterable, Iterator, Optional

from .engine import FileContext, ProjectContext, dotted_name
from .project_model import MethodModel, ProjectModel, build_project_model

__all__ = [
    "AotKeyModel", "MeshSite", "Prov", "SpmdModel", "attr_provenance",
    "build_spmd_model", "expr_prov", "is_mesh_class", "mentions_mesh",
    "shard_graph_dict", "shard_graph_dot",
]

# ------------------------------------------------------------------ lattice

HOST = "host"
DEVICE = "device"
REPLICATED = "replicated"
SHARDED = "sharded"
UNKNOWN = "unknown"

_DEVICE_SIDE = frozenset({DEVICE, REPLICATED, SHARDED})


@dataclass(frozen=True)
class Prov:
    """One lattice point; ``axes`` only for ``sharded``."""

    kind: str
    axes: tuple = ()

    @property
    def device_side(self) -> bool:
        return self.kind in _DEVICE_SIDE


P_HOST = Prov(HOST)
P_DEVICE = Prov(DEVICE)
P_REPLICATED = Prov(REPLICATED)
P_UNKNOWN = Prov(UNKNOWN)


def join_prov(a: Prov, b: Prov) -> Prov:
    """Lattice join: equal points stay, device-side points collapse to
    ``device``, and a host/device mix is ``unknown`` (never flagged —
    precision over recall, like the guard inference)."""
    if a == b:
        return a
    if a.device_side and b.device_side:
        return P_DEVICE
    return P_UNKNOWN


#: call prefixes that build HOST arrays
_HOST_PREFIXES = ("np.", "numpy.")
#: call prefixes that build DEVICE arrays (committed, jit-consumable)
_DEVICE_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "jax.nn.",
                    "jax.random.")
_DEVICE_PUT = frozenset({"jax.device_put", "device_put"})
#: blessed upload helpers: the engine's ``self._dev()`` and the
#: parallel.sharding constructors — the sanctioned mesh-mode paths
_REPLICATED_HELPERS = frozenset({"replicated"})
_SHARDED_HELPERS = frozenset({
    "shard_llama_params", "apply_shardings", "llama_page_pool_sharding",
})

_SHARD_MAP_NAMES = frozenset({
    "shard_map", "jax.shard_map", "jax.experimental.shard_map.shard_map",
})
_PSPEC_NAMES = frozenset({
    "P", "PartitionSpec", "jax.sharding.PartitionSpec",
})
_MESH_CTORS = frozenset({
    "Mesh", "jax.sharding.Mesh", "AbstractMesh", "jax.sharding.AbstractMesh",
})
#: helper functions whose axis names are resolved from their own body
_MESH_BUILDERS = frozenset({"build_mesh"})

#: array constructors whose arguments carry PROGRAM SHAPE — a config field
#: reaching one of these inside an engine class shapes the compiled program
#: even when ``_build_programs`` never reads it directly (the row built in
#: ``__init__`` and handed to the dispatch is the ``device_stop_width`` case)
_SHAPE_CTORS = frozenset({
    "jnp.zeros", "jnp.ones", "jnp.full", "jnp.empty", "jnp.arange",
    "jnp.asarray", "jax.numpy.zeros", "jax.numpy.ones", "jax.numpy.full",
    "jax.numpy.empty", "jax.numpy.arange", "jax.numpy.asarray",
    "jax.random.split",
})

_AOT_KEY_FNS = frozenset({"serving_programs", "aot_compile"})
_CONFIG_CLASS = "EngineConfig"
#: spellings a config object goes by inside the engine/scheduler
_CONFIG_RECEIVERS = frozenset({
    "config", "cfg", "self.config", "self.cfg", "self._config",
})
_PROGRAM_BUILDER = "_build_programs"
_PROGRAM_SOURCE = "step_programs"   # its result's parts are dispatches too

#: affix match needs this much signal before "prefix_page_size" may cover
#: key "page_size" (equality is always enough)
_MIN_AFFIX = 5


# -------------------------------------------------------------- mesh scopes


def mentions_mesh(node: ast.AST) -> bool:
    """Does this scope reference a mesh at all? (SH01's function test.)"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in ("mesh", "_mesh"):
            return True
        if isinstance(sub, ast.Name) and sub.id == "mesh":
            return True
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = sub.args
            names = [p.arg for p in list(args.posonlyargs) + list(args.args)
                     + list(args.kwonlyargs)]
            if "mesh" in names:
                return True
    return False


def is_mesh_class(cls: ast.ClassDef) -> bool:
    """``self.mesh = ...`` anywhere (even ``= None``) marks the whole class
    as mesh-mode code — the engine idiom SH01 keys on."""
    for sub in ast.walk(cls):
        if isinstance(sub, (ast.Assign, ast.AnnAssign)):
            targets = sub.targets if isinstance(sub, ast.Assign) \
                else [sub.target]
            for t in targets:
                if isinstance(t, ast.Attribute) \
                        and t.attr in ("mesh", "_mesh") \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == "self":
                    return True
    return False


def bare_device_puts(scope: ast.AST) -> Iterator[ast.Call]:
    """Destination-less ``jax.device_put`` calls (SH01's primitive)."""
    for sub in ast.walk(scope):
        if not isinstance(sub, ast.Call):
            continue
        if dotted_name(sub.func) not in _DEVICE_PUT:
            continue
        has_dst = len(sub.args) >= 2 or any(
            kw.arg and ("shard" in kw.arg or kw.arg in ("device", "dst"))
            for kw in sub.keywords)
        if not has_dst:
            yield sub


# ------------------------------------------------------------------- model


@dataclass(frozen=True)
class MeshSite:
    """One mesh construction with its (resolved) axis names."""

    path: str
    tier: str
    owner: str                # "Class.method" / function / "<module>"
    ctor: str                 # "Mesh" | "AbstractMesh" | "build_mesh"
    axes: tuple               # resolved literal axis names ("" when opaque)
    line: int


@dataclass
class AotKeyModel:
    """EngineConfig fields vs the AOT cache-key parameter set."""

    config_path: str = ""
    fields: tuple = ()
    #: key-tuple parameter names, unioned over serving_programs/aot_compile
    key_names: frozenset = frozenset()
    key_sites: list = field(default_factory=list)   # [(path, fn name)]
    engine_cls: str = ""
    engine_path: str = ""
    #: config field -> (witness text, line in engine file)
    shape_fields: dict = field(default_factory=dict)
    #: shape-affecting fields with no name-matched key parameter
    uncovered: list = field(default_factory=list)


class SpmdModel:
    """The whole-program SPMD picture rules/spmd.py runs over."""

    def __init__(self) -> None:
        self.race: Optional[ProjectModel] = None
        self.meshes: list[MeshSite] = []
        self.axis_universe: frozenset = frozenset()
        #: (path, class name) of mesh-mode classes
        self.mesh_classes: set = set()
        #: (path, function name) of mesh-mode module functions
        self.mesh_functions: set = set()
        #: (path, cls) -> {attr: line} for ``self.X = jax.jit(...)``
        self.dispatch_attrs: dict = {}
        #: (path, cls) -> {attr: Prov} joined over assignment sites
        self.attr_prov: dict = {}
        #: method qualkey -> (chain, path, line, direct qualkey) when a call
        #: path reaches a destination-less device_put
        self.bare_upload_via: dict = {}
        self.aot: Optional[AotKeyModel] = None


def build_spmd_model(project: ProjectContext) -> SpmdModel:
    """Pass 3 over every file in the run (memoized on the context)."""
    cached = getattr(project, "_spmd_model", None)
    if cached is not None:
        return cached
    model = SpmdModel()
    model.race = build_project_model(project)
    _collect_meshes(model, project)
    _collect_mesh_scopes(model, project)
    _collect_dispatches_and_prov(model, project)
    _compute_bare_uploads(model)
    model.aot = _build_aot_model(project)
    project._spmd_model = model
    return model


# ------------------------------------------------------------ mesh inventory


def _walk_with_owner(tree: ast.AST) -> Iterator[tuple[ast.AST, str]]:
    """Yield (node, owner qualname) — the enclosing class.method/function."""

    def rec(node: ast.AST, owner: str) -> Iterator[tuple[ast.AST, str]]:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                yield child, owner
                yield from rec(child, child.name)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                sub = f"{owner}.{child.name}" if owner != "<module>" \
                    else child.name
                yield child, owner
                yield from rec(child, sub)
            else:
                yield child, owner
                yield from rec(child, owner)

    yield from rec(tree, "<module>")


def _literal_axes(call: ast.Call) -> tuple:
    """Axis names when spelled literally: 2nd positional arg or the
    ``axis_names=`` kwarg, a tuple/list of string constants (a single
    string constant also counts, matching jax). () when opaque."""
    cand: Optional[ast.AST] = None
    if len(call.args) >= 2:
        cand = call.args[1]
    for kw in call.keywords:
        if kw.arg == "axis_names":
            cand = kw.value
    if cand is None and dotted_name(call.func).rsplit(".", 1)[-1] == \
            "AbstractMesh":
        # AbstractMesh(shape_tuple) with ((name, size), ...) pairs
        if call.args:
            cand = call.args[0]
    if isinstance(cand, ast.Constant) and isinstance(cand.value, str):
        return (cand.value,)
    axes: list[str] = []
    if isinstance(cand, (ast.Tuple, ast.List)):
        for el in cand.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                axes.append(el.value)
            elif isinstance(el, (ast.Tuple, ast.List)) and el.elts and \
                    isinstance(el.elts[0], ast.Constant) and \
                    isinstance(el.elts[0].value, str):
                axes.append(el.elts[0].value)      # (name, size) pair
            else:
                return ()                           # partially opaque
    return tuple(axes)


def _collect_meshes(model: SpmdModel, project: ProjectContext) -> None:
    # first the literal Mesh/AbstractMesh sites; builder axes resolve after
    builder_axes: dict[str, tuple] = {}
    builder_sites: list[tuple[FileContext, str, ast.Call]] = []
    for ctx in project.files:
        for node, owner in _walk_with_owner(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            terminal = name.rsplit(".", 1)[-1]
            if name in _MESH_CTORS:
                axes = _literal_axes(node)
                model.meshes.append(MeshSite(
                    ctx.relpath, ctx.tier, owner, terminal, axes,
                    node.lineno))
            elif terminal in _MESH_BUILDERS:
                builder_sites.append((ctx, owner, node))
    # a builder's axes are the union of literal Mesh axes inside its def
    for ctx in project.files:
        for node, _owner in _walk_with_owner(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in _MESH_BUILDERS:
                axes: tuple = ()
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) and \
                            dotted_name(sub.func) in _MESH_CTORS:
                        axes = axes + tuple(
                            a for a in _literal_axes(sub) if a not in axes)
                if axes:
                    builder_axes[node.name] = axes
    for ctx, owner, call in builder_sites:
        terminal = dotted_name(call.func).rsplit(".", 1)[-1]
        model.meshes.append(MeshSite(
            ctx.relpath, ctx.tier, owner, terminal,
            builder_axes.get(terminal, ()), call.lineno))
    model.meshes.sort(key=lambda s: (s.path, s.line))
    universe: set[str] = set()
    for site in model.meshes:
        universe.update(site.axes)
    model.axis_universe = frozenset(universe)


def _collect_mesh_scopes(model: SpmdModel, project: ProjectContext) -> None:
    for ctx in project.files:
        for node in ctx.tree.body:
            if isinstance(node, ast.ClassDef) and is_mesh_class(node):
                model.mesh_classes.add((ctx.relpath, node.name))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and mentions_mesh(node):
                model.mesh_functions.add((ctx.relpath, node.name))


# ----------------------------------------------- provenance + dispatch map


def expr_prov(expr: ast.AST, env: Optional[dict] = None,
              attr_prov: Optional[dict] = None) -> Prov:
    """Provenance of one expression under a local environment (name ->
    Prov) and a class attribute map (attr -> Prov). Anything unmodeled is
    ``unknown`` — the lattice errs toward silence."""
    env = env or {}
    attr_prov = attr_prov or {}
    if isinstance(expr, ast.IfExp):
        return join_prov(expr_prov(expr.body, env, attr_prov),
                         expr_prov(expr.orelse, env, attr_prov))
    if isinstance(expr, (ast.Subscript, ast.Starred)):
        return expr_prov(expr.value, env, attr_prov)
    if isinstance(expr, ast.Name):
        return env.get(expr.id, P_UNKNOWN)
    if isinstance(expr, ast.Attribute):
        if isinstance(expr.value, ast.Name) and expr.value.id in \
                ("self", "cls"):
            return attr_prov.get(expr.attr, P_UNKNOWN)
        return P_UNKNOWN
    if isinstance(expr, (ast.List, ast.ListComp)):
        return P_HOST
    if not isinstance(expr, ast.Call):
        return P_UNKNOWN
    name = dotted_name(expr.func)
    terminal = name.rsplit(".", 1)[-1]
    if name.startswith(_HOST_PREFIXES):
        return P_HOST
    if terminal == "tolist" or name.startswith("list"):
        return P_HOST
    if name.startswith(_DEVICE_PREFIXES):
        return P_DEVICE
    if terminal == "_dev" or terminal in _REPLICATED_HELPERS:
        return P_REPLICATED
    if terminal in _SHARDED_HELPERS:
        return Prov(SHARDED)
    if name in _DEVICE_PUT:
        dst = expr.args[1] if len(expr.args) >= 2 else None
        for kw in expr.keywords:
            if kw.arg and ("shard" in kw.arg or kw.arg in ("device", "dst")):
                dst = kw.value
        if dst is None:
            return P_DEVICE            # bare: committed, default device
        spec = _named_sharding_spec(dst)
        if spec is not None:
            axes = tuple(a for a in spec if a)
            return Prov(SHARDED, axes) if axes else P_REPLICATED
        return P_DEVICE
    return P_UNKNOWN


def _named_sharding_spec(expr: ast.AST) -> Optional[tuple]:
    """``NamedSharding(mesh, P("tp", None))`` -> ("tp", None); None when
    the expression is not a literal NamedSharding/PartitionSpec."""
    if isinstance(expr, ast.Call):
        name = dotted_name(expr.func).rsplit(".", 1)[-1]
        if name == "NamedSharding" and len(expr.args) >= 2:
            return _named_sharding_spec(expr.args[1])
        if dotted_name(expr.func) in _PSPEC_NAMES or name == "PartitionSpec":
            spec: list = []
            for a in expr.args:
                if isinstance(a, ast.Constant):
                    spec.append(a.value if isinstance(a.value, str) else None)
                elif isinstance(a, (ast.Tuple, ast.List)):
                    inner = [e.value for e in a.elts
                             if isinstance(e, ast.Constant)
                             and isinstance(e.value, str)]
                    spec.append(tuple(inner) if inner else None)
                else:
                    return None          # variable axis — opaque
            return tuple(spec)
    return None


def attr_provenance(cls: ast.ClassDef) -> dict:
    """attr -> joined Prov over every ``self.X = expr`` site in the class
    (subscript stores mutate in place and do not rebind)."""
    out: dict[str, Prov] = {}
    for sub in ast.walk(cls):
        if not isinstance(sub, (ast.Assign, ast.AnnAssign)):
            continue
        targets = sub.targets if isinstance(sub, ast.Assign) \
            else [sub.target]
        value = sub.value
        if value is None:
            continue
        for t in targets:
            if isinstance(t, ast.Attribute) and \
                    isinstance(t.value, ast.Name) and t.value.id == "self":
                p = expr_prov(value, attr_prov=out)
                prev = out.get(t.attr)
                out[t.attr] = p if prev is None else join_prov(prev, p)
    return out


def _collect_dispatches_and_prov(model: SpmdModel,
                                 project: ProjectContext) -> None:
    from .engine import _is_jit_expr
    for ctx in project.files:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            key = (ctx.relpath, node.name)
            dispatches: dict[str, int] = {}
            for sub in ast.walk(node):
                if isinstance(sub, ast.Assign) and \
                        isinstance(sub.value, ast.Call) and (
                            _is_jit_expr(sub.value.func)
                            or dotted_name(sub.value.func) == _PROGRAM_SOURCE):
                    for t in sub.targets:
                        for leaf in getattr(t, "elts", [t]):
                            if isinstance(leaf, ast.Attribute) and \
                                    isinstance(leaf.value, ast.Name) and \
                                    leaf.value.id == "self":
                                dispatches.setdefault(leaf.attr, sub.lineno)
            if dispatches:
                model.dispatch_attrs[key] = dispatches
            if key in model.mesh_classes:
                model.attr_prov[key] = attr_provenance(node)


# ------------------------------------------------------- bare-upload chains


def _compute_bare_uploads(model: SpmdModel) -> None:
    """method qualkey -> (chain, path, line, direct qualkey) whenever some
    resolved call path performs a destination-less device_put."""
    race = model.race
    assert race is not None
    direct: dict[tuple, tuple] = {}
    for cm in race.classes.values():
        for m in cm.methods.values():
            for call in bare_device_puts(m.node):
                k = race.method_key(m)
                direct.setdefault(k, ((m.qualname,), cm.relpath, call.lineno))
                break
    memo = model.bare_upload_via
    in_progress: set[tuple] = set()

    def visit(m: MethodModel):
        key = race.method_key(m)
        if key in memo:
            return memo[key]
        if key in in_progress:
            return None
        if key in direct:
            chain, path, line = direct[key]
            memo[key] = (chain, path, line, key)
            return memo[key]
        in_progress.add(key)
        found = None
        for ev in m.calls:
            callee = race.resolve_call(m.cls, ev)
            if callee is None:
                continue
            sub = visit(callee)
            if sub is not None:
                found = ((m.qualname,) + sub[0], sub[1], sub[2], sub[3])
                break
        in_progress.discard(key)
        if found is not None:
            memo[key] = found
        return found

    for cm in race.classes.values():
        for m in cm.methods.values():
            visit(m)


# ------------------------------------------------------------ AOT key model


def _config_class(project: ProjectContext
                  ) -> Optional[tuple[FileContext, ast.ClassDef]]:
    for ctx in sorted(project.files, key=lambda c: c.relpath):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef) and node.name == _CONFIG_CLASS:
                return ctx, node
    return None


def _config_deps(expr: ast.AST, fields: frozenset, env: dict,
                 attr_fields: dict, method_reads: dict) -> set:
    """Config fields an expression's value depends on: direct
    ``config.<f>`` / ``self.config.<f>`` reads, locals from ``env``,
    derived ``self.<attr>`` reads from ``attr_fields``, and config method
    calls resolved through ``method_reads``."""
    deps: set[str] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute):
            recv = dotted_name(node.value)
            if recv in _CONFIG_RECEIVERS and node.attr in fields:
                deps.add(node.attr)
            elif isinstance(node.value, ast.Name) and \
                    node.value.id in ("self", "cls"):
                deps.update(attr_fields.get(node.attr, ()))
        elif isinstance(node, ast.Name):
            deps.update(env.get(node.id, ()))
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                dotted_name(node.func.value) in _CONFIG_RECEIVERS:
            deps.update(method_reads.get(node.func.attr, ()))
    return deps


class _EngineScan:
    """One forward pass over an engine class: the derived-attr field map,
    the shape-constructor witness set, and the ``_build_programs`` read
    set — all threaded through per-method local environments."""

    def __init__(self, fields: frozenset, method_reads: dict):
        self.fields = fields
        self.method_reads = method_reads
        self.attr_fields: dict[str, set] = {}
        #: field -> (witness, line)
        self.ctor_reads: dict[str, tuple] = {}
        self.builder_reads: dict[str, tuple] = {}

    def scan_class(self, cls: ast.ClassDef) -> None:
        methods = [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        # the derived-attr map needs a short fixpoint (attrs defined from
        # other attrs, e.g. self._spec_w = self.spec_k + 1)
        for _ in range(3):
            before = {a: set(s) for a, s in self.attr_fields.items()}
            for fn in methods:
                self._scan_method(cls.name, fn, record=False)
            if before == self.attr_fields:
                break
        for fn in methods:
            self._scan_method(cls.name, fn, record=True)

    def _deps(self, expr: ast.AST, env: dict) -> set:
        return _config_deps(expr, self.fields, env, self.attr_fields,
                            self.method_reads)

    def _scan_method(self, cls_name: str, fn: ast.AST,
                     record: bool) -> None:
        env: dict[str, set] = {}
        in_builder = fn.name == _PROGRAM_BUILDER

        def visit_expr(expr: ast.AST, line: int) -> None:
            if not record:
                return
            if in_builder:
                for f in self._deps(expr, env):
                    self.builder_reads.setdefault(f, (
                        f"read in {cls_name}.{fn.name}", line))
            for node in ast.walk(expr):
                if isinstance(node, ast.Call) and \
                        dotted_name(node.func) in _SHAPE_CTORS:
                    parts = list(node.args) + [kw.value
                                               for kw in node.keywords]
                    for a in parts:
                        for f in self._deps(a, env):
                            self.ctor_reads.setdefault(f, (
                                f"shapes a device array in "
                                f"{cls_name}.{fn.name} via "
                                f"{dotted_name(node.func)}(...)",
                                node.lineno))

        def walk(body: list) -> None:
            for stmt in body:
                if isinstance(stmt, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign)):
                    value = getattr(stmt, "value", None)
                    if value is None:
                        continue
                    deps = self._deps(value, env)
                    visit_expr(value, stmt.lineno)
                    targets = stmt.targets if isinstance(stmt, ast.Assign) \
                        else [stmt.target]
                    for t in targets:
                        if isinstance(t, ast.Name):
                            if isinstance(stmt, ast.AugAssign):
                                deps = deps | env.get(t.id, set())
                            env[t.id] = deps
                        elif isinstance(t, ast.Attribute) and \
                                isinstance(t.value, ast.Name) and \
                                t.value.id == "self":
                            cur = self.attr_fields.setdefault(t.attr, set())
                            cur.update(deps)
                elif isinstance(stmt, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    walk(stmt.body)      # jitted closures read outer locals
                else:
                    for child in ast.iter_child_nodes(stmt):
                        if isinstance(child, (ast.stmt,
                                              ast.ExceptHandler)):
                            continue
                        visit_expr(child, getattr(stmt, "lineno", 0))
                    for name in ("body", "orelse", "finalbody"):
                        sub = getattr(stmt, name, None)
                        if isinstance(sub, list) and sub and \
                                isinstance(sub[0], ast.stmt):
                            walk(sub)
                    for h in getattr(stmt, "handlers", []):
                        walk(h.body)
                    for case in getattr(stmt, "cases", []):
                        walk(case.body)

        walk(fn.body)


def _names_match(field_name: str, key: str) -> bool:
    """``prefix_page_size`` covers key ``page_size``; ``scheduler_spec_k``
    covers ``spec_k``; short names must match exactly."""
    if field_name == key:
        return True
    if min(len(field_name), len(key)) < _MIN_AFFIX:
        return False
    return (field_name.startswith(key) or key.startswith(field_name)
            or field_name.endswith(key) or key.endswith(field_name))


def _build_aot_model(project: ProjectContext) -> Optional[AotKeyModel]:
    found = _config_class(project)
    if found is None:
        return None
    cfg_ctx, cfg_cls = found
    aot = AotKeyModel(config_path=cfg_ctx.relpath)
    fields = tuple(
        t.target.id for t in cfg_cls.body
        if isinstance(t, ast.AnnAssign) and isinstance(t.target, ast.Name))
    aot.fields = fields
    fset = frozenset(fields)
    method_reads: dict[str, set] = {}
    for node in cfg_cls.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            reads = {sub.attr for sub in ast.walk(node)
                     if isinstance(sub, ast.Attribute)
                     and isinstance(sub.value, ast.Name)
                     and sub.value.id == "self" and sub.attr in fset}
            if reads:
                method_reads[node.name] = reads

    # the AOT key parameter set
    key_names: set[str] = set()
    for ctx in project.files:
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in _AOT_KEY_FNS:
                a = node.args
                for p in list(a.posonlyargs) + list(a.args) + \
                        list(a.kwonlyargs):
                    if p.arg != "self":
                        key_names.add(p.arg)
                aot.key_sites.append((ctx.relpath, node.name))
    aot.key_names = frozenset(key_names)
    aot.key_sites.sort()

    # the engine class: the one defining _build_programs
    for ctx in sorted(project.files, key=lambda c: c.relpath):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            if not any(isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))
                       and n.name == _PROGRAM_BUILDER for n in node.body):
                continue
            scan = _EngineScan(fset, method_reads)
            scan.scan_class(node)
            for f, (witness, line) in sorted(scan.builder_reads.items()):
                aot.shape_fields.setdefault(f, (witness, line))
            for f, (witness, line) in sorted(scan.ctor_reads.items()):
                aot.shape_fields.setdefault(f, (witness, line))
            if not aot.engine_cls:
                aot.engine_cls = node.name
                aot.engine_path = ctx.relpath

    if aot.key_sites:
        aot.uncovered = sorted(
            f for f in aot.shape_fields
            if not any(_names_match(f, k) for k in aot.key_names))
    return aot


# ------------------------------------------------------------ graph emitters


def shard_graph_dict(model: SpmdModel) -> dict:
    """The inferred SPMD world as a stable JSON-able dict — the committed
    ``docs/shard_graph.json`` artifact (line numbers excluded so the drift
    check churns on structure, not on unrelated edits)."""
    meshes = [
        {"path": s.path, "owner": s.owner, "ctor": s.ctor,
         "axes": list(s.axes)}
        for s in model.meshes
    ]
    dispatches = [
        {"path": path, "class": cls, "attr": attr}
        for (path, cls), attrs in sorted(model.dispatch_attrs.items())
        for attr in sorted(attrs)
    ]
    provenance = [
        {"path": path, "class": cls, "attr": attr, "prov": p.kind
         + (f"({','.join(p.axes)})" if p.axes else "")}
        for (path, cls), attrs in sorted(model.attr_prov.items())
        for attr, p in sorted(attrs.items())
        if p.kind in (HOST, REPLICATED, SHARDED)
    ]
    aot: dict = {}
    if model.aot is not None:
        aot = {
            "config": model.aot.config_path,
            "engine": model.aot.engine_cls,
            "keys": sorted(model.aot.key_names),
            "key_sites": [{"path": p, "fn": f}
                          for p, f in model.aot.key_sites],
            "shape_fields": {
                f: w for f, (w, _line)
                in sorted(model.aot.shape_fields.items())},
            "uncovered": list(model.aot.uncovered),
        }
    return {
        "version": 1,
        "axes": sorted(model.axis_universe),
        "meshes": meshes,
        "mesh_classes": [{"path": p, "class": c}
                         for p, c in sorted(model.mesh_classes)],
        "dispatches": dispatches,
        "provenance": provenance,
        "aot_key": aot,
    }


def shard_graph_dot(model: SpmdModel) -> str:
    """Graphviz DOT: mesh sites -> their axes, engine -> dispatch attrs,
    uncovered AOT fields red."""
    lines = ["digraph shard_world {", '  rankdir="LR";',
             '  node [shape=box, fontname="monospace"];']
    for a in sorted(model.axis_universe):
        lines.append(f'  "axis:{a}" [shape=ellipse];')
    seen: set[str] = set()
    for s in model.meshes:
        label = f"{s.owner} ({s.ctor})"
        if label in seen:
            continue
        seen.add(label)
        lines.append(f'  "{label}" [tooltip="{s.path}"];')
        for a in s.axes:
            lines.append(f'  "{label}" -> "axis:{a}";')
    for (path, cls), attrs in sorted(model.dispatch_attrs.items()):
        lines.append(f'  "{cls}" [tooltip="{path}"];')
        for attr in sorted(attrs):
            lines.append(f'  "{cls}" -> "{cls}.{attr}" [style=dashed];')
    if model.aot is not None:
        for f in model.aot.uncovered:
            lines.append(f'  "field:{f}" [color="red", penwidth=2];')
    lines.append("}")
    return "\n".join(lines) + "\n"
