"""GSPMD sharding rule family: the tp>1 serving plane's contracts.

SCALING.md round 18's guarantee — token-identical serving across
tp=1/2/4/8 — holds only while the sharded plane keeps three disciplines
that GSPMD itself never enforces:

- **unconstrained-sharding** (moved here from the jax family when it
  went interprocedural): a jit root in a mesh-context module whose
  reachable body never constrains a sharding leaves every intermediate
  at GSPMD's default — replicated — which silently serializes the tp
  mesh. Constraint evidence is now found ANYWHERE the whole-repo graph
  can reach from the root, not just in the defining module.
- **unknown-mesh-axis**: `PartitionSpec` axis names are strings; GSPMD
  treats an axis the mesh doesn't declare as "replicate", so
  ``P("tensor")`` where the mesh says ``tp`` is not an error anywhere —
  it is a silent 8x memory/compute regression. Literal specs are
  validated against the declared table (``MESH_AXES`` in
  engine/sharded/geometry.py; a standalone file may declare its own).
- **sharded-host-pull**: `jax.device_get` (and placement-free
  `jax.device_put`, which implicitly reshards onto the default device)
  on the sharded serving path gathers a distributed value through one
  host — the all-gather the sharded plane exists to avoid. The ONE
  per-decision result pull is legitimate and pragma-justified.
- **donated-buffer-escape**: `donate_argnums` on a jit site in a
  mesh-context module that declares no shardings for the donated
  positions (no ``in_shardings``, no bound sharding bundle) — XLA can
  only alias donated buffers whose input and output shardings match, so
  a donation that escapes the `EngineShardings` bundle degrades to a
  silent copy (donation wasted) or an implicit reshard of a dead buffer.
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.graftlint.core import (
    FileContext,
    Finding,
    LintRule,
    body_walk,
    dotted_name,
)
from tools.graftlint.rules.jaxpurity import (
    _is_jit_call,
    _jit_wrap_info,
    _loop_scope,
    _wrapped_bare_name_of,
)

# Names whose presence marks a module as MESH-CONTEXT: it builds or
# consumes a device mesh, so its jitted programs run under GSPMD and
# every per-op default is "replicate" unless somebody says otherwise.
_MESH_MARKERS = frozenset({
    "Mesh", "NamedSharding", "PartitionSpec", "make_mesh",
    "mesh_from_config", "shard_map", "shard_params", "build_plane",
    "kv_cache_spec", "serving_param_specs", "EngineShardings",
})
# Calls that constitute sharding evidence inside a traced function.
_CONSTRAINT_CALLS = frozenset({
    "with_sharding_constraint", "constrain", "device_put",
})


def _mesh_context(ctx: FileContext) -> bool:
    for node in ctx.all_nodes():
        if isinstance(node, ast.ImportFrom):
            if any(a.name in _MESH_MARKERS for a in node.names):
                return True
        elif isinstance(node, (ast.Name, ast.Attribute)):
            name = dotted_name(node)
            if name and name.rsplit(".", 1)[-1] in _MESH_MARKERS:
                return True
    return False


class UnconstrainedSharding(LintRule):
    id = "unconstrained-sharding"
    family = "sharding"
    description = (
        "a jit root in a mesh-context module whose inputs never see a "
        "sharding constraint — GSPMD defaults every unconstrained "
        "intermediate to replicated, silently serializing the tp mesh"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        # Runtime modules only (+ the fixture corpus): tests/tools jit
        # abstract shapes whose shardings ride in ShapeDtypeStructs the
        # AST cannot see.
        if not _loop_scope(ctx.name):
            return
        if not _mesh_context(ctx):
            return
        repo = ctx.repo
        jit_roots = repo.jit_roots()
        # Local jit call sites: in_/out_shardings kwargs, or a
        # functools.partial binding a sharding bundle by keyword
        # (`jax.jit(functools.partial(_impl, shardings=...))` — the
        # engine's idiom) are constraint evidence for the wrapped name.
        constrained: set[str] = set()
        sites: dict[str, ast.Call] = {}
        for node in ctx.all_nodes():
            if isinstance(node, ast.Call) and _is_jit_call(node) and node.args:
                bare = _wrapped_bare_name_of(node.args[0])
                if not bare:
                    continue
                if self._site_constrained(node):
                    constrained.add(bare)
                else:
                    sites.setdefault(bare, node)
        for qual, func, _cls in ctx.graph_funcs():
            g = ctx.gqual(qual)
            if g not in jit_roots:
                continue
            bare = qual.rsplit(".", 1)[-1]
            if bare in constrained:
                continue
            # the interprocedural upgrade: constraint evidence counts
            # wherever the repo graph can reach from this root — the
            # engine's jitted impls call constrain() helpers that live
            # in parallel/sharding.py, two modules away
            if repo.reaches(g, self._entry_constrains, dispatch="strict"):
                continue
            site = sites.get(bare, func)
            yield ctx.finding(
                self, site,
                f"jit root `{qual}` in a mesh-context module never "
                f"constrains a sharding (no with_sharding_constraint/"
                f"constrain/device_put reachable, no in_/out_shardings, "
                f"no bound sharding bundle) — GSPMD will replicate every "
                f"input across the mesh; thread an EngineShardings bundle "
                f"or justify via pragma",
            )

    @staticmethod
    def _entry_constrains(entry) -> bool:
        for call in entry.calls:
            name = call["n"]
            if name.rsplit(".", 1)[-1] in _CONSTRAINT_CALLS:
                return True
            # method call on a sharding bundle: shardings.kv5(x)
            if "shard" in name.split(".", 1)[0]:
                return True
        return False

    @staticmethod
    def _site_constrained(call: ast.Call) -> bool:
        if any(
            kw.arg in ("in_shardings", "out_shardings", "in_specs", "out_specs")
            for kw in call.keywords
        ):
            return True
        wrapped = call.args[0]
        if isinstance(wrapped, ast.Call) and dotted_name(wrapped.func) in (
            "partial", "functools.partial", "named_program",
        ):
            return any(
                kw.arg and "shard" in kw.arg for kw in wrapped.keywords
            )
        return False


class UnknownMeshAxis(LintRule):
    id = "unknown-mesh-axis"
    family = "sharding"
    description = (
        "a PartitionSpec string literal naming an axis the declared "
        "mesh-axes table (engine/sharded/geometry.MESH_AXES) does not "
        "contain — GSPMD silently replicates along a typo'd axis"
    )

    _TABLE_MODULE = "engine/sharded/geometry.py"
    _TABLE_NAME = "MESH_AXES"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _loop_scope(ctx.name):
            return
        repo = ctx.repo
        axes = repo.str_tuple(self._TABLE_MODULE, self._TABLE_NAME)
        if axes is None:
            # standalone files (fixtures, snippets) may carry their own
            # declaration; without ANY table there is nothing to check
            idx = repo.modules.get(ctx.name)
            axes = idx.str_tuples.get(self._TABLE_NAME) if idx else None
        if not axes:
            return
        known = set(axes)
        # local aliases of PartitionSpec (`from jax.sharding import
        # PartitionSpec as P` is the repo idiom)
        aliases = {"PartitionSpec"}
        for node in ctx.all_nodes():
            if isinstance(node, ast.ImportFrom):
                for a in node.names:
                    if a.name == "PartitionSpec":
                        aliases.add(a.asname or a.name)
        for node in ctx.all_nodes():
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if not name:
                continue
            if name not in aliases and name.rsplit(".", 1)[-1] != "PartitionSpec":
                continue
            for arg in node.args:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Constant) and isinstance(sub.value, str) \
                            and sub.value not in known:
                        yield ctx.finding(
                            self, sub,
                            f"PartitionSpec names axis `{sub.value}`, which "
                            f"the declared mesh-axes table "
                            f"({self._TABLE_NAME} = {tuple(sorted(known))}) "
                            f"does not contain — GSPMD treats an undeclared "
                            f"axis as 'replicate', so this spec silently "
                            f"stops sharding; fix the axis name or add it "
                            f"to the table",
                        )


def _sharded_seed_module(name: str) -> bool:
    """Modules whose functions seed the tp>1 serving path: the sharded
    plane package itself, plus sharded fixtures (which stand in for a
    plane module in the self-contained corpus)."""
    if "engine/sharded/" in name:
        return True
    return "fixtures/graftlint" in name and "sharded" in name.rsplit("/", 1)[-1]


class ShardedHostPull(LintRule):
    id = "sharded-host-pull"
    family = "sharding"
    description = (
        "jax.device_get (or placement-free jax.device_put, an implicit "
        "reshard) reachable from the tp>1 serving path — gathers a "
        "distributed value through one host"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _loop_scope(ctx.name):
            return
        repo = ctx.repo
        seeds = [
            g for g in repo.funcs
            if _sharded_seed_module(repo.func_module[g])
        ]
        if not seeds:
            return
        reach = repo.reachable(frozenset(seeds), dispatch="strict")
        for qual, func, _cls in ctx.graph_funcs():
            if ctx.gqual(qual) not in reach:
                continue
            for node in body_walk(func):
                if not isinstance(node, ast.Call):
                    continue
                msg = self._classify(node)
                if msg:
                    yield ctx.finding(
                        self, node,
                        f"{msg} inside `{qual}`, reachable from the sharded "
                        f"serving plane — on a tp>1 mesh this gathers the "
                        f"full distributed value through one host, the "
                        f"exact all-gather the sharded plane exists to "
                        f"avoid; keep results device-resident (or justify "
                        f"the single per-decision pull via pragma)",
                    )

    @staticmethod
    def _classify(call: ast.Call) -> str | None:
        name = dotted_name(call.func)
        if name in ("jax.device_get", "device_get"):
            return f"host pull `{name}(...)`"
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr == "addressable_data":
            return "host pull `.addressable_data()`"
        if name == "jax.device_put" and len(call.args) < 2 and not any(
            kw.arg in ("device", "sharding", "donate") for kw in call.keywords
        ):
            return "placement-free `jax.device_put(...)` (implicit reshard)"
        return None


class DonatedBufferEscape(LintRule):
    id = "donated-buffer-escape"
    family = "sharding"
    description = (
        "donate_argnums on a jit site in a mesh-context module with no "
        "declared shardings — XLA only aliases donations whose in/out "
        "shardings match, so the donation escapes the EngineShardings "
        "bundle and degrades to a silent copy"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _loop_scope(ctx.name):
            return
        if not _mesh_context(ctx):
            return
        for node in ctx.all_nodes():
            if not isinstance(node, ast.Call):
                continue
            info = _jit_wrap_info(node)
            if info is None or not info[3]:  # no donate_argnums
                continue
            if self._site_declares_shardings(node):
                continue
            yield ctx.finding(
                self, node,
                f"jit site donates positions {info[3]} but declares no "
                f"shardings (no in_shardings, no bound sharding bundle) "
                f"in a mesh-context module — XLA cannot alias a donated "
                f"buffer across mismatched shardings, so the donation "
                f"silently degrades to a copy (and the caller still "
                f"treats the input as dead); thread the EngineShardings "
                f"bundle or justify via pragma",
            )

    @staticmethod
    def _site_declares_shardings(call: ast.Call) -> bool:
        if any(
            kw.arg in ("in_shardings", "out_shardings")
            for kw in call.keywords
        ):
            return True
        wrapped = call.args[0]
        if isinstance(wrapped, ast.Call) and dotted_name(wrapped.func) in (
            "partial", "functools.partial", "named_program",
        ):
            return any(kw.arg and "shard" in kw.arg for kw in wrapped.keywords)
        return False


SHARDING_RULES: list[LintRule] = [
    UnconstrainedSharding(),
    UnknownMeshAxis(),
    ShardedHostPull(),
    DonatedBufferEscape(),
]
