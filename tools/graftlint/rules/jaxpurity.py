"""JAX purity rule family: tracer/host-sync discipline in jit'd code.

Everything on the decision hot path lives inside `jax.jit` (CONTRIBUTING
ground rule); these rules keep the jit boundary honest:

- **host syncs** (`.item()`, `.tolist()`, `np.asarray`, `jax.device_get`,
  `float()/int()` on array-shaped expressions) inside any function
  REACHABLE from a `@jax.jit` / `jax.jit(fn)` / `shard_map` root are a
  trace-time error at best, a silent per-call device round trip at worst;
- **Python-side mutation** of closed-over / self state inside traced code
  runs once at trace time and never again — the classic "my counter
  stopped at 1" bug;
- **static_argnums** positions must receive hashable values (a list/dict
  literal at a static position raises at every call; a mutable default
  on a static parameter raises on the first defaulted call);
- a buffer passed at a **donate_argnums** position is dead after the
  call — reusing it reads deallocated (or aliased-output) memory.

Reachability rides the whole-repo interprocedural graph (``ctx.repo``,
tools/graftlint/repograph.py) under STRICT dispatch: jit roots are
decorated defs plus every def whose bare name any module wraps in
`jax.jit(...)`/`shard_map(...)` — the module that DEFINES a jitted
function is usually not the one that jits it (engine/engine.py jits
models/llama.py's forwards), and with one graph the llama helpers are
analyzed no matter which file asked. Strict dispatch never guesses an
unannotated receiver, which keeps "reachable from a jit root"
false-positive-poor. The sharding-specific rules that used to live here
moved to the ``sharding`` family (rules/sharding.py) when they went
interprocedural.
"""

from __future__ import annotations

import ast
from typing import Iterable, Iterator

from tools.graftlint.core import (
    FileContext,
    Finding,
    LintRule,
    body_walk,
    dotted_name,
)

_JIT_WRAPPERS = ("jax.jit", "jit", "pjit", "jax.pjit")
_SHMAP_WRAPPERS = ("shard_map", "jax.shard_map")


def _is_jit_call(call: ast.Call) -> bool:
    name = dotted_name(call.func)
    return name in _JIT_WRAPPERS or name in _SHMAP_WRAPPERS


def _wrapped_bare_name_of(node: ast.AST) -> str:
    """The bare function name a jit/shard_map call wraps, seeing through
    `functools.partial(fn, ...)` (the engine's idiom for binding closure
    constants: `jax.jit(functools.partial(_wave_impl, ...))`)."""
    if isinstance(node, ast.Call) and dotted_name(node.func) in (
        "partial", "functools.partial", "named_program",
    ) and node.args:
        node = node.args[0]
    name = dotted_name(node)
    return name.rsplit(".", 1)[-1] if name else ""


def _is_jit_decorator(dec: ast.AST) -> bool:
    """@jax.jit, @jit, @partial(jax.jit, ...), @functools.partial(jax.jit)."""
    if dotted_name(dec) in _JIT_WRAPPERS:
        return True
    if isinstance(dec, ast.Call):
        name = dotted_name(dec.func)
        if name in _JIT_WRAPPERS or name in _SHMAP_WRAPPERS:
            return True
        if name in ("partial", "functools.partial") and dec.args:
            return dotted_name(dec.args[0]) in _JIT_WRAPPERS + _SHMAP_WRAPPERS
    return False


def jit_reachable_here(ctx: FileContext) -> list[tuple[str, ast.AST]]:
    """This file's functions (local qual + def node) that the whole-repo
    graph says are reachable from a jit/shard_map root, memoized per
    file. The graph walks callees across modules; the AST walk for the
    actual hazard classification stays local to this file."""
    cached = getattr(ctx, "_jit_reachable_here", None)
    if cached is not None:
        return cached
    repo = ctx.repo
    roots = repo.jit_roots()
    out: list[tuple[str, ast.AST]] = []
    if roots:
        reach = repo.reachable(roots, dispatch="strict")
        for qual, node, _cls in ctx.graph_funcs():
            if ctx.gqual(qual) in reach:
                out.append((qual, node))
    ctx._jit_reachable_here = out
    return out


_HOST_SYNC_METHODS = ("item", "tolist", "numpy", "block_until_ready")
_HOST_SYNC_CALLS = (
    "jax.device_get", "device_get", "np.asarray", "numpy.asarray",
    "np.array", "numpy.array",
)


class HostSyncInJit(LintRule):
    id = "jit-host-sync"
    family = "jax"
    description = (
        "host synchronization (.item(), np.asarray, jax.device_get, "
        "float()/int() on arrays) inside a function reachable from a "
        "jax.jit/shard_map root"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for qual, func in jit_reachable_here(ctx):
            for node in body_walk(func):
                if not isinstance(node, ast.Call):
                    continue
                msg = self._classify(node)
                if msg:
                    yield ctx.finding(
                        self, node,
                        f"{msg} inside `{qual}`, which is reachable from a "
                        f"jit/shard_map root — a trace-time error or a "
                        f"silent per-call device round trip; move host "
                        f"conversion outside the traced function",
                    )

    @staticmethod
    def _classify(call: ast.Call) -> str | None:
        if isinstance(call.func, ast.Attribute) and call.func.attr in _HOST_SYNC_METHODS:
            return f"host sync `.{call.func.attr}()`"
        name = dotted_name(call.func)
        if name in _HOST_SYNC_CALLS:
            return f"host sync `{name}(...)`"
        if name in ("float", "int", "bool") and call.args:
            arg = call.args[0]
            # Heuristic: only array-shaped expressions (attribute chains,
            # subscripts) — bare names and literals are usually Python
            # scalars / static args and would drown the signal.
            if isinstance(arg, (ast.Attribute, ast.Subscript)):
                return f"host sync `{name}()` on `{ast.unparse(arg)}`"
        return None


_MUTATORS = (
    "append", "extend", "add", "update", "pop", "remove", "insert",
    "setdefault", "clear", "popitem", "discard",
)


class ClosureMutationInJit(LintRule):
    id = "jit-closure-mutation"
    family = "jax"
    description = (
        "Python-level mutation of closed-over/self state inside traced "
        "code — it runs once at trace time, then never again"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for qual, func in jit_reachable_here(ctx):
            local = self._local_names(func)
            for node in body_walk(func):
                if isinstance(node, (ast.Global, ast.Nonlocal)):
                    kind = "global" if isinstance(node, ast.Global) else "nonlocal"
                    yield ctx.finding(
                        self, node,
                        f"`{kind} {', '.join(node.names)}` inside traced "
                        f"`{qual}` — the rebind happens at trace time only",
                    )
                elif isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                    for t in targets:
                        if (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id in ("self", "cls")
                        ):
                            yield ctx.finding(
                                self, t,
                                f"write to `{ast.unparse(t)}` inside traced "
                                f"`{qual}` happens at trace time only (and "
                                f"leaks a tracer into object state)",
                            )
                elif isinstance(node, ast.Expr):
                    # Only DISCARDED results: `updates = optimizer.update(...)`
                    # is the pure optax idiom, `seen.append(x)` is the bug.
                    call = node.value
                    if (
                        isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Attribute)
                        and call.func.attr in _MUTATORS
                        and isinstance(call.func.value, ast.Name)
                        and call.func.value.id not in local
                    ):
                        yield ctx.finding(
                            self, call,
                            f"`.{call.func.attr}()` on closed-over "
                            f"`{call.func.value.id}` inside traced `{qual}` "
                            f"mutates host state at trace time only",
                        )

    @staticmethod
    def _local_names(func: ast.AST) -> set[str]:
        a = func.args
        names = {
            arg.arg
            for arg in a.posonlyargs + a.args + a.kwonlyargs
            + ([a.vararg] if a.vararg else []) + ([a.kwarg] if a.kwarg else [])
        }
        for node in body_walk(func):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            names.add(n.id)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.For, ast.AsyncFor)):
                t = node.target
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        names.add(n.id)
            elif isinstance(node, (ast.withitem,)) and node.optional_vars is not None:
                for n in ast.walk(node.optional_vars):
                    if isinstance(n, ast.Name):
                        names.add(n.id)
            elif isinstance(node, ast.comprehension):
                for n in ast.walk(node.target):
                    if isinstance(n, ast.Name):
                        names.add(n.id)
        return names


def _kw_const_list(keywords: list[ast.keyword], kw_name: str, typ: type) -> list:
    """Constant values of type `typ` in keyword `kw_name` (scalar or
    tuple/list literal); [] when absent or not statically resolvable."""
    for kw in keywords:
        if kw.arg != kw_name:
            continue
        if isinstance(kw.value, (ast.Tuple, ast.List)):
            return [
                el.value for el in kw.value.elts
                if isinstance(el, ast.Constant) and isinstance(el.value, typ)
            ]
        if isinstance(kw.value, ast.Constant) and isinstance(kw.value.value, typ):
            return [kw.value.value]
    return []


def _jit_wrap_info(
    call: ast.Call,
) -> tuple[str, list[int], list[str], list[int], int] | None:
    """(wrapped bare name, static_argnums, static_argnames,
    donate_argnums, positional offset) for a `jax.jit(fn, ...)` call;
    None for anything else.

    Sees through `functools.partial(fn, ...)` like the root collector
    does; `offset` is the number of POSITIONAL args the partial binds —
    static/donate positions refer to the partial's (shifted) signature,
    so checks against the underlying def must add it. The engine's idiom
    binds closure constants by KEYWORD (offset 0)."""
    if dotted_name(call.func) not in _JIT_WRAPPERS or not call.args:
        return None
    wrapped = call.args[0]
    offset = 0
    if isinstance(wrapped, ast.Call) and dotted_name(wrapped.func) in (
        "partial", "functools.partial", "named_program",
    ) and wrapped.args:
        offset = len(wrapped.args) - 1
        wrapped = wrapped.args[0]
    bare = dotted_name(wrapped)
    bare = bare.rsplit(".", 1)[-1] if bare else ""
    return (
        bare,
        _kw_const_list(call.keywords, "static_argnums", int),
        _kw_const_list(call.keywords, "static_argnames", str),
        _kw_const_list(call.keywords, "donate_argnums", int),
        offset,
    )


_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)


class NonHashableStatic(LintRule):
    id = "jit-static-hashable"
    family = "jax"
    description = (
        "a static_argnums/static_argnames position receiving an unhashable "
        "value (list/dict/set literal, or a mutable default) — TypeError "
        "at every call"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        # this file's defs by bare name (the wrapped def and its jit wrap
        # normally share a module; cross-module default-checking is the
        # repo graph's job and not worth the noise here)
        by_bare: dict[str, list[ast.AST]] = {}
        for func, _cls in ctx.functions():
            by_bare.setdefault(func.name, []).append(func)
        # jitted-name -> (static positions, static names); covers
        # `name = jax.jit(fn, static_argnums=...)` and
        # `self._x = jax.jit(fn, ...)` assignments.
        jitted: dict[str, tuple[list[int], list[str]]] = {}
        for node in ctx.all_nodes():
            if not isinstance(node, ast.Assign):
                continue
            if not isinstance(node.value, ast.Call):
                continue
            info = _jit_wrap_info(node.value)
            if info is None:
                continue
            bare, nums, names, _don, offset = info
            for t in node.targets:
                tn = dotted_name(t)
                if tn and (nums or names):
                    jitted[tn] = (nums, names)
            # mutable default on a static parameter of the wrapped fn
            for func in by_bare.get(bare, []):
                yield from self._check_func_defaults(ctx, func, nums, names, offset)
        # decorated functions: defaults + direct call sites by name
        for func, cls in ctx.functions():
            for dec in getattr(func, "decorator_list", []):
                if isinstance(dec, ast.Call) and _is_jit_decorator(dec):
                    nums = _kw_const_list(dec.keywords, "static_argnums", int)
                    names = _kw_const_list(dec.keywords, "static_argnames", str)
                    if nums or names:
                        jitted.setdefault(func.name, (nums, names))
                        yield from self._check_func_defaults(ctx, func, nums, names)
        # call sites
        for node in ctx.all_nodes():
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name not in jitted:
                continue
            nums, names = jitted[name]
            for pos in nums:
                if pos < len(node.args) and isinstance(node.args[pos], _UNHASHABLE):
                    yield ctx.finding(
                        self, node.args[pos],
                        f"unhashable literal at static_argnums position {pos} "
                        f"of jitted `{name}` — static args are dict keys of "
                        f"the compile cache; pass a tuple or a scalar",
                    )
            for kw in node.keywords:
                if kw.arg in names and isinstance(kw.value, _UNHASHABLE):
                    yield ctx.finding(
                        self, kw.value,
                        f"unhashable literal for static_argnames "
                        f"`{kw.arg}` of jitted `{name}` — pass a tuple or a "
                        f"scalar",
                    )

    def _check_func_defaults(
        self, ctx, func, nums, names, offset=0
    ) -> Iterator[Finding]:
        a = func.args
        params = a.posonlyargs + a.args
        defaults = [None] * (len(params) - len(a.defaults)) + list(a.defaults)
        for pos, (param, default) in enumerate(zip(params, defaults)):
            # static positions are in the (possibly partial-shifted)
            # wrapped signature; underlying param `pos` sits at
            # wrapped position `pos - offset`
            static = (pos - offset) in nums or param.arg in names
            if static and isinstance(default, _UNHASHABLE):
                yield ctx.finding(
                    self, default,
                    f"static parameter `{param.arg}` of `{func.name}` has an "
                    f"unhashable default — the first defaulted call raises "
                    f"TypeError",
                )


_LOOP_SYNC_METHODS = ("block_until_ready", "item")
_LOOP_SYNC_CALLS = (
    "np.asarray", "numpy.asarray", "jax.device_get", "device_get",
)


def _loop_scope(name: str) -> bool:
    """Runtime modules only (same discipline as the resilience family's
    raw-clock rule): the engine/sched hot paths are where a per-iteration
    sync costs a dispatch-pipeline stall; tests, tools, and bench.py sync
    deliberately. The fixture corpus stays in scope so the detector stays
    testable."""
    if name.startswith("k8s_llm_scheduler_tpu/"):
        return True
    return "fixtures/graftlint" in name


class DeviceSyncInLoop(LintRule):
    id = "device-sync-in-loop"
    family = "jax"
    description = (
        "host-device synchronization (.block_until_ready()/.item()/"
        "np.asarray/jax.device_get) inside a for/while body in a runtime "
        "module — per-iteration syncs serialize the dispatch pipeline"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _loop_scope(ctx.name):
            return
        seen: set[int] = set()  # nested loops must not double-report
        for node in ctx.all_nodes():
            if not isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
                continue
            # Only the BODY repeats: the loop's iterable/test expressions
            # run once (or once per re-check, host-side), and an `else:`
            # clause executes exactly once after the loop — neither is a
            # per-iteration sync.
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if not isinstance(sub, ast.Call) or id(sub) in seen:
                        continue
                    msg = self._classify(sub)
                    if msg:
                        seen.add(id(sub))
                        yield ctx.finding(
                            self, sub,
                            f"{msg} inside a loop body — one host round "
                            f"trip PER ITERATION is the synchronization "
                            f"boundary the fused decode runtime exists to "
                            f"remove (Kernel Looping); hoist the sync out "
                            f"of the loop, batch it into one device_get, "
                            f"or justify via pragma",
                        )

    @staticmethod
    def _classify(call: ast.Call) -> str | None:
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in _LOOP_SYNC_METHODS:
            return f"device sync `.{call.func.attr}()`"
        name = dotted_name(call.func)
        if name in _LOOP_SYNC_CALLS:
            return f"device sync `{name}(...)`"
        return None


class DonatedBufferReuse(LintRule):
    id = "jit-donated-reuse"
    family = "jax"
    description = (
        "a variable passed at a donate_argnums position is read again "
        "after the call — the buffer was donated and may alias the output"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        donating: dict[str, list[int]] = {}
        for node in ctx.all_nodes():
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                info = _jit_wrap_info(node.value)
                if info and info[3]:
                    for t in node.targets:
                        tn = dotted_name(t)
                        if tn:
                            donating[tn] = info[3]
        for func, _cls in ctx.functions():
            for dec in getattr(func, "decorator_list", []):
                if isinstance(dec, ast.Call) and _is_jit_decorator(dec):
                    don = _kw_const_list(dec.keywords, "donate_argnums", int)
                    if don:
                        donating.setdefault(func.name, don)
        if not donating:
            return
        for func, _cls in ctx.functions():
            yield from self._check_body(ctx, func, donating)

    def _check_body(
        self, ctx: FileContext, func: ast.AST, donating: dict[str, list[int]]
    ) -> Iterator[Finding]:
        # linear pass: donated bare-name args are dead from the call's line
        # until reassigned
        dead: dict[str, int] = {}  # name -> line it was donated at
        for node in body_walk(func):
            if isinstance(node, ast.Call):
                positions = donating.get(dotted_name(node.func))
                if positions:
                    for pos in positions:
                        if pos < len(node.args):
                            name = node.args[pos]
                            if isinstance(name, ast.Name):
                                dead[name.id] = node.lineno
        if not dead:
            return
        assigns: dict[str, list[int]] = {}
        for node in body_walk(func):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name) and n.id in dead:
                            assigns.setdefault(n.id, []).append(node.lineno)
        for node in body_walk(func):
            if not (isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load)):
                continue
            donated_at = dead.get(node.id)
            if donated_at is None or node.lineno <= donated_at:
                continue
            # a reassignment at/after the donation revives the name (the
            # idiomatic `pages = _append(pages, ...)` rebinds on the
            # donation line itself)
            if any(donated_at <= a <= node.lineno for a in assigns.get(node.id, [])):
                continue
            yield ctx.finding(
                self, node,
                f"`{node.id}` was donated at line {donated_at} "
                f"(donate_argnums) and is read again here — the buffer is "
                f"deallocated or aliased by the output; use the returned "
                f"value instead",
            )


JAX_RULES: list[LintRule] = [
    HostSyncInJit(),
    ClosureMutationInJit(),
    NonHashableStatic(),
    DeviceSyncInLoop(),
    DonatedBufferReuse(),
]
