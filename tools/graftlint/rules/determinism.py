"""Determinism rule family: byte-replayability of the trace plane.

The repo's replay story (sim/trace.canonical_bytes, the chaos harness's
canonical_chaos_bytes, the decision journal) is
a BYTE contract: two runs with the same seed must serialize identical
artifacts, and the digests in rollout/registry.py make any divergence a
hard failure. Python offers four quiet ways to break that contract and
none of them is a runtime error:

- **unordered-set-in-canonical**: iterating a ``set`` yields
  hash-randomized order (PYTHONHASHSEED varies per process for str
  keys). If that order flows into a function that reaches a canonical
  writer, two identical runs serialize different bytes. Dicts are
  exempt on purpose — insertion order is a language guarantee since
  3.7, and the canonical writers sort keys anyway; it is specifically
  ``set`` iteration that has NO deterministic order.
- **unseeded-random**: ``random.*`` / ``np.random.*`` module-level
  functions use interpreter-global state no replay harness can pin
  per-component. Runtime modules must thread a ``random.Random(seed)``
  / ``np.random.default_rng(seed)`` instance (or a JAX PRNG key).
- **id-keyed-ordering**: ``id()`` is an address — it differs across
  runs by construction. Sorting by it, or keying a serialized mapping
  with it, bakes ASLR into the artifact.
- **wall-clock-in-replay**: a wall/monotonic clock read inside a
  function that reaches a canonical writer lands a nondeterministic
  value in a replay-compared payload. (The resilience family's
  raw-clock rule polices clock INJECTION discipline broadly; this rule
  is the narrow byte-contract version, scoped to writer-reaching
  functions only.)

"Reaches a canonical writer" rides the whole-repo graph: the writer
sink set is every call site flagged ``w`` at index time —
canonical_*_bytes, ``json.dump(s)`` with ``sort_keys=True`` (the repo's
canonical-JSON convention), and fed ``hashlib`` digest constructors.
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
from tools.graftlint.rules.jaxpurity import _loop_scope


def _entry_writes_canonical(entry) -> bool:
    return any(c.get("w") for c in entry.calls)


def _writer_reaching_funcs(ctx: FileContext) -> list[tuple[str, ast.AST]]:
    """This file's functions from which the repo graph can reach a
    canonical-writer call site (the function's own body counts).
    Memoized per file: every rule in this family scopes on it."""
    cached = getattr(ctx, "_writer_reaching", None)
    if cached is not None:
        return cached
    repo = ctx.repo
    out: list[tuple[str, ast.AST]] = []
    for qual, node, _cls in ctx.graph_funcs():
        if repo.reaches(
            ctx.gqual(qual), _entry_writes_canonical, dispatch="strict"
        ):
            out.append((qual, node))
    ctx._writer_reaching = out
    return out


def _set_typed_names(func: ast.AST) -> set[str]:
    """Local names bound to set-valued expressions anywhere in `func`
    (linear approximation — good enough for the build-then-serialize
    shape these payload functions all have)."""
    names: set[str] = set()
    for node in body_walk(func):
        if isinstance(node, ast.Assign) and _is_set_expr(node.value, names):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
        elif isinstance(node, ast.AnnAssign) and node.value is not None \
                and isinstance(node.target, ast.Name) \
                and _is_set_expr(node.value, names):
            names.add(node.target.id)
    return names


def _is_set_expr(node: ast.AST, set_names: set[str]) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        name = dotted_name(node.func)
        if name == "set" or name == "frozenset":
            return True
        # set-producing methods/operations on known sets
        if isinstance(node.func, ast.Attribute) and node.func.attr in (
            "union", "intersection", "difference", "symmetric_difference",
        ):
            return _is_set_expr(node.func.value, set_names)
    if isinstance(node, ast.Name):
        return node.id in set_names
    if isinstance(node, ast.BinOp) and isinstance(
        node.op, (ast.BitOr, ast.BitAnd, ast.Sub, ast.BitXor)
    ):
        return _is_set_expr(node.left, set_names) or _is_set_expr(
            node.right, set_names
        )
    return False


# Consumers whose result does not depend on argument order: a
# comprehension/generator fed straight into one of these launders the
# set's hash-randomized order away, so its iteration is harmless.
_ORDER_FREE_CONSUMERS = frozenset({
    "sorted", "min", "max", "sum", "len", "any", "all", "set", "frozenset",
})


class UnorderedSetInCanonical(LintRule):
    id = "unordered-set-in-canonical"
    family = "determinism"
    description = (
        "iteration over a set (hash-randomized order) inside a function "
        "that reaches a canonical-JSON/trace/digest writer, without an "
        "intervening sorted() — two identical runs serialize different "
        "bytes"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _loop_scope(ctx.name):
            return
        for qual, func in _writer_reaching_funcs(ctx):
            set_names = _set_typed_names(func)
            # `sorted(x for x in some_set)` is the FIX, not the bug: a
            # comprehension handed straight to an order-insensitive
            # consumer never leaks the set's order into the payload
            order_free: set[int] = set()
            for node in body_walk(func):
                if isinstance(node, ast.Call) \
                        and dotted_name(node.func) in _ORDER_FREE_CONSUMERS:
                    for a in node.args:
                        if isinstance(a, (ast.ListComp, ast.SetComp,
                                          ast.GeneratorExp)):
                            order_free.add(id(a))
            for node in body_walk(func):
                iters: list[ast.AST] = []
                if isinstance(node, (ast.For, ast.AsyncFor)):
                    iters = [node.iter]
                elif isinstance(node, (ast.ListComp, ast.SetComp,
                                       ast.DictComp, ast.GeneratorExp)):
                    if id(node) in order_free:
                        continue
                    iters = [gen.iter for gen in node.generators]
                for it in iters:
                    # `for x in sorted(s)` is the fix, not the bug: only
                    # the raw set expression itself is unordered
                    if _is_set_expr(it, set_names):
                        yield ctx.finding(
                            self, it,
                            f"iteration over a set in `{qual}`, which "
                            f"reaches a canonical writer — set order is "
                            f"hash-randomized per process, so the "
                            f"serialized bytes differ across identical "
                            f"runs; wrap the set in sorted(...) before "
                            f"iterating",
                        )


_RANDOM_GLOBAL_FNS = frozenset({
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "sample", "uniform", "gauss", "normalvariate", "betavariate",
    "expovariate", "triangular", "getrandbits", "seed",
})
_NP_RANDOM_OK = frozenset({"default_rng", "Generator", "SeedSequence"})


class UnseededRandom(LintRule):
    id = "unseeded-random"
    family = "determinism"
    description = (
        "random.* / np.random.* module-level (global-state) call in a "
        "replayable runtime module — thread a seeded Random/default_rng "
        "instance instead"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _loop_scope(ctx.name):
            return
        for node in ctx.all_nodes():
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if not name:
                continue
            head, _, rest = name.partition(".")
            if head == "random" and rest in _RANDOM_GLOBAL_FNS:
                yield ctx.finding(
                    self, node,
                    f"`{name}(...)` uses the interpreter-global RNG — "
                    f"replay cannot pin its state per component; thread a "
                    f"`random.Random(seed)` instance (or derive from the "
                    f"run's seed) instead",
                )
            elif head in ("np", "numpy") and rest.startswith("random."):
                fn = rest.split(".", 1)[1]
                if fn not in _NP_RANDOM_OK:
                    yield ctx.finding(
                        self, node,
                        f"`{name}(...)` uses numpy's legacy global RNG — "
                        f"replay cannot pin its state per component; use "
                        f"`np.random.default_rng(seed)` and thread the "
                        f"generator",
                    )


class IdKeyedOrdering(LintRule):
    id = "id-keyed-ordering"
    family = "determinism"
    description = (
        "id()-derived ordering or mapping keys in a function that "
        "reaches a canonical writer — id() is an address, different "
        "every run"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _loop_scope(ctx.name):
            return
        for qual, func in _writer_reaching_funcs(ctx):
            for node in body_walk(func):
                if isinstance(node, ast.Call):
                    for kw in node.keywords:
                        if kw.arg == "key" and self._mentions_id(kw.value):
                            yield ctx.finding(
                                self, kw.value,
                                f"sort key derived from id() in `{qual}`, "
                                f"which reaches a canonical writer — id() "
                                f"is a memory address, so the order (and "
                                f"the serialized bytes) changes every run; "
                                f"sort by a stable field instead",
                            )
                elif isinstance(node, ast.Dict):
                    for k in node.keys:
                        if k is not None and self._mentions_id(k):
                            yield ctx.finding(
                                self, k,
                                f"mapping keyed by id() in `{qual}`, which "
                                f"reaches a canonical writer — the keys "
                                f"are addresses and differ across runs; "
                                f"key by a stable identifier",
                            )
                elif isinstance(node, ast.Subscript) and isinstance(
                    node.ctx, ast.Store
                ) and self._mentions_id(node.slice):
                    yield ctx.finding(
                        self, node.slice,
                        f"store keyed by id() in `{qual}`, which reaches "
                        f"a canonical writer — key by a stable identifier",
                    )

    @staticmethod
    def _mentions_id(node: ast.AST) -> bool:
        if isinstance(node, ast.Name) and node.id == "id":
            return True  # key=id
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and dotted_name(sub.func) == "id":
                return True
        return False


_CLOCK_CALLS = frozenset({
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns",
    "datetime.now", "datetime.utcnow",
    "datetime.datetime.now", "datetime.datetime.utcnow",
})


class WallClockInReplay(LintRule):
    id = "wall-clock-in-replay"
    family = "determinism"
    description = (
        "a wall/monotonic clock read inside a function that reaches a "
        "canonical writer — a nondeterministic value in a "
        "replay-compared payload"
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not _loop_scope(ctx.name):
            return
        for qual, func in _writer_reaching_funcs(ctx):
            for node in body_walk(func):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                if name in _CLOCK_CALLS:
                    yield ctx.finding(
                        self, node,
                        f"`{name}()` inside `{qual}`, which reaches a "
                        f"canonical writer — a raw clock value in a "
                        f"replay-compared payload breaks the byte "
                        f"contract; use the injected clock (the kvplane/"
                        f"chaos pattern) or keep timestamps out of the "
                        f"canonical payload",
                    )


DETERMINISM_RULES: list[LintRule] = [
    UnorderedSetInCanonical(),
    UnseededRandom(),
    IdKeyedOrdering(),
    WallClockInReplay(),
]
