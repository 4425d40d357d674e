"""Whole-repo interprocedural engine: module/symbol index, call graph,
and the reachability/dataflow API the rule families build on.

graftlint v1 analyzed one file at a time; anything cross-module rode an
ad-hoc name prepass (`_global_jit_names`) re-scanning the tree per
process. The contracts the repo actually cares about are cross-module
and path-shaped — "is this function reachable from a jit root that
engine/engine.py wrapped around a models/llama.py def", "does every
swap path also reach a generation bump" — so v2 builds ONE repo-wide
index and answers those questions from it.

Three layers:

1. **ModuleIndex** — everything the graph needs about one file, extracted
   in a single AST pass and JSON-serializable: the function table
   (qualified defs, async-ness, decorators), per-function call sites
   (dotted names + line numbers + canonical-writer flags), per-function
   AugAssign attribute evidence (``self.prefix_epoch += 1`` is epoch-bump
   evidence for the protocol family), import bindings, class tables
   (bases, attribute types inferred from ``self.x = ClassName(...)``),
   local/param type bindings, ``jax.jit``/``shard_map`` wrap sites (with
   static/donate positions, seeing through ``functools.partial``),
   PartitionSpec literal axes, and module-level string-tuple constants
   (the MESH_AXES declaration reads through this).

2. **RepoGraph** — the merged view plus call resolution. Every function
   gets a global qualname ``relpath::Class.method``. A call site resolves
   under one of two dispatch policies:

   - ``strict``: bare names to same-module defs or followed through the
     import table into the defining module; ``self.x()``/``cls.x()`` to
     the owning class (then bases); ``obj.m()`` through the receiver's
     inferred type (parameter annotation, ``x = ClassName(...)`` local
     binding, or a class attribute typed in ``__init__``). Unresolvable
     receivers produce NO edge — strict never guesses, so "reachable
     from a jit root" stays false-positive-poor.
   - ``bare``: strict, plus unresolved ``obj.m()`` attribute calls link
     to every repo def named ``m`` (common container-method names are
     blocked). Generous linking is the right polarity for the protocol
     family, where reaching MORE evidence can only suppress findings.

3. **Reachability API** — ``reachable(seeds, dispatch=...)`` (memoized
   per seed-set) and ``reaches(start, pred, dispatch=...)`` ("from this
   function, is a call site / AugAssign matching `pred` reachable?").

The on-disk cache (``.graftlint_cache.json``, content-hash-keyed per
module) makes the index incremental: an unchanged file is never
re-parsed, so the full-repo `cli lint` keeps its <10s fast-tier budget
and a single-file edit re-indexes exactly that file.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
from pathlib import Path
from typing import Callable, Iterable, Iterator

INDEX_VERSION = 2
CACHE_BASENAME = ".graftlint_cache.json"

_JIT_WRAPPERS = ("jax.jit", "jit", "pjit", "jax.pjit")
_SHMAP_WRAPPERS = ("shard_map", "jax.shard_map")
# `named_program` (engine/engine.py) is a partial that names the program
_PARTIAL_NAMES = ("partial", "functools.partial", "named_program")

# Attribute-call names too generic to bare-link: every container and a
# handful of repo-wide conventions (start/stop/close/run appear on dozens
# of unrelated classes; linking them would weld the graph into one blob).
_BARE_DISPATCH_BLOCKLIST = frozenset({
    "append", "extend", "add", "update", "pop", "remove", "insert", "get",
    "items", "keys", "values", "setdefault", "clear", "copy", "join",
    "split", "strip", "encode", "decode", "format", "read", "write",
    "close", "open", "start", "stop", "run", "put", "send", "recv",
    "acquire", "release", "wait", "notify", "set", "result", "done",
    "submit", "cancel", "sort", "index", "count", "popitem", "discard",
})


def dotted(node: ast.AST) -> str:
    """'a.b.c' for Name/Attribute chains, '' for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jit_name(name: str) -> bool:
    return name in _JIT_WRAPPERS or name in _SHMAP_WRAPPERS


def _const_ints(keywords: list[ast.keyword], kw: str) -> list[int]:
    for k in keywords:
        if k.arg != kw:
            continue
        if isinstance(k.value, (ast.Tuple, ast.List)):
            return [
                e.value for e in k.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)
            ]
        if isinstance(k.value, ast.Constant) and isinstance(k.value.value, int):
            return [k.value.value]
    return []


def _const_strs(keywords: list[ast.keyword], kw: str) -> list[str]:
    for k in keywords:
        if k.arg != kw:
            continue
        if isinstance(k.value, (ast.Tuple, ast.List)):
            return [
                e.value for e in k.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            ]
        if isinstance(k.value, ast.Constant) and isinstance(k.value.value, str):
            return [k.value.value]
    return []


def _is_canonical_writer(call: ast.Call, name: str) -> bool:
    """A call site that serializes into a replay-compared / digested
    artifact: the named canonical_* writers, json.dump(s) with
    sort_keys=True (the repo's canonical-JSON convention), and hashlib
    digest constructors fed data."""
    last = name.rsplit(".", 1)[-1]
    if last in (
        "canonical_bytes", "canonical_chaos_bytes",
        "canonical_blackbox_bytes", "save_trace",
    ):
        return True
    if name in ("json.dumps", "json.dump"):
        return any(
            kw.arg == "sort_keys"
            and isinstance(kw.value, ast.Constant) and kw.value.value is True
            for kw in call.keywords
        )
    if name.startswith("hashlib.") and last in (
        "blake2b", "sha256", "sha1", "md5", "blake2s",
    ):
        return bool(call.args)
    return False


class FuncEntry:
    """One function/method in the index (JSON round-trippable)."""

    __slots__ = (
        "qual", "name", "cls", "lineno", "is_async", "parent",
        "jit_decorated", "calls", "aug_attrs", "var_types",
    )

    def __init__(
        self, qual: str, name: str, cls: str | None, lineno: int,
        is_async: bool, parent: str | None, jit_decorated: bool,
        calls: list[dict], aug_attrs: list[str], var_types: dict[str, str],
    ) -> None:
        self.qual = qual
        self.name = name
        self.cls = cls
        self.lineno = lineno
        self.is_async = is_async
        self.parent = parent
        self.jit_decorated = jit_decorated
        # calls: [{"n": dotted, "l": lineno, "w": canonical-writer flag}]
        self.calls = calls
        self.aug_attrs = aug_attrs
        self.var_types = var_types

    def to_json(self) -> dict:
        return {
            "qual": self.qual, "name": self.name, "cls": self.cls,
            "lineno": self.lineno, "is_async": self.is_async,
            "parent": self.parent, "jit_decorated": self.jit_decorated,
            "calls": self.calls, "aug_attrs": self.aug_attrs,
            "var_types": self.var_types,
        }

    @classmethod
    def from_json(cls, d: dict) -> "FuncEntry":
        return cls(
            d["qual"], d["name"], d["cls"], d["lineno"], d["is_async"],
            d["parent"], d["jit_decorated"], d["calls"], d["aug_attrs"],
            d["var_types"],
        )


class ModuleIndex:
    """Everything the graph needs about one module, one AST pass."""

    __slots__ = (
        "path", "functions", "classes", "imports", "jit_wraps",
        "jit_assign_targets", "pspec_names", "str_tuples",
    )

    def __init__(self, path: str) -> None:
        self.path = path
        self.functions: dict[str, FuncEntry] = {}   # local qual -> entry
        # class name -> {"bases": [...], "methods": [...], "attrs": {a: T}}
        self.classes: dict[str, dict] = {}
        self.imports: dict[str, str] = {}           # local name -> source
        # [{"wrapped": bare, "target": dotted-or-"", "lineno": int,
        #   "static_argnums": [...], "static_argnames": [...],
        #   "donate_argnums": [...], "offset": int, "site_kws": [...],
        #   "partial_kws": [...]}]
        self.jit_wraps: list[dict] = []
        self.jit_assign_targets: list[str] = []
        self.str_tuples: dict[str, list[str]] = {}
        # local names bound to jax.sharding.PartitionSpec ("P", ...)
        self.pspec_names: list[str] = []

    # ------------------------------------------------------------- build
    @classmethod
    def build(cls, path: str, tree: ast.Module) -> "ModuleIndex":
        idx = cls(path)
        idx._imports(tree)
        idx._module_level(tree)
        idx._functions(tree)
        return idx

    def _imports(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    src = f"{node.module}.{a.name}"
                    self.imports[a.asname or a.name] = src
                    if src == "jax.sharding.PartitionSpec":
                        self.pspec_names.append(a.asname or a.name)
        if "PartitionSpec" not in self.pspec_names:
            self.pspec_names.append("PartitionSpec")

    def _module_level(self, tree: ast.Module) -> None:
        for node in tree.body:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            if not isinstance(t, ast.Name):
                continue
            if isinstance(node.value, (ast.Tuple, ast.List)):
                strs = [
                    e.value for e in node.value.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                ]
                if strs and len(strs) == len(node.value.elts):
                    self.str_tuples[t.id] = strs

    @staticmethod
    def _jit_wrap_record(call: ast.Call, target: str) -> dict | None:
        """A `jax.jit(fn, ...)` / `shard_map(fn, ...)` value site, seeing
        through functools.partial; None for anything else."""
        name = dotted(call.func)
        if not _is_jit_name(name) or not call.args:
            return None
        wrapped = call.args[0]
        offset = 0
        partial_kws: list[str] = []
        if isinstance(wrapped, ast.Call) and dotted(wrapped.func) in _PARTIAL_NAMES \
                and wrapped.args:
            offset = len(wrapped.args) - 1
            partial_kws = [kw.arg for kw in wrapped.keywords if kw.arg]
            wrapped = wrapped.args[0]
        bare = dotted(wrapped)
        bare = bare.rsplit(".", 1)[-1] if bare else ""
        if not bare:
            return None
        return {
            "wrapped": bare,
            "target": target,
            "lineno": call.lineno,
            "static_argnums": _const_ints(call.keywords, "static_argnums"),
            "static_argnames": _const_strs(call.keywords, "static_argnames"),
            "donate_argnums": _const_ints(call.keywords, "donate_argnums"),
            "offset": offset,
            "site_kws": [kw.arg for kw in call.keywords if kw.arg],
            "partial_kws": partial_kws,
        }

    def _functions(self, tree: ast.Module) -> None:
        idx = self

        def jit_decorator(dec: ast.AST) -> bool:
            if _is_jit_name(dotted(dec)):
                return True
            if isinstance(dec, ast.Call):
                name = dotted(dec.func)
                if _is_jit_name(name):
                    return True
                if name in _PARTIAL_NAMES and dec.args:
                    return _is_jit_name(dotted(dec.args[0]))
            return False

        def extract_func(
            func: ast.FunctionDef | ast.AsyncFunctionDef,
            cls_name: str | None, parent: str | None,
        ) -> FuncEntry:
            qual = func.name if cls_name is None else f"{cls_name}.{func.name}"
            if parent is not None:
                qual = f"{parent}.<locals>.{func.name}"
            calls: list[dict] = []
            aug_attrs: list[str] = []
            var_types: dict[str, str] = {}
            for arg in (
                func.args.posonlyargs + func.args.args + func.args.kwonlyargs
            ):
                ann = arg.annotation
                if ann is not None:
                    ann_name = dotted(ann)
                    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
                        ann_name = ann.value.strip('"')
                    if ann_name:
                        var_types[arg.arg] = ann_name
            # one body walk, not descending into nested defs
            stack: list[ast.AST] = list(ast.iter_child_nodes(func))
            while stack:
                node = stack.pop()
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef, ast.Lambda)):
                    continue
                if isinstance(node, ast.Call):
                    name = dotted(node.func)
                    if name:
                        rec = {"n": name, "l": node.lineno}
                        if _is_canonical_writer(node, name):
                            rec["w"] = True
                        calls.append(rec)
                elif isinstance(node, ast.AugAssign):
                    t = node.target
                    if isinstance(t, ast.Attribute):
                        aug_attrs.append(t.attr)
                    elif isinstance(t, ast.Name):
                        aug_attrs.append(t.id)
                elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and isinstance(node.value, ast.Call):
                    callee = dotted(node.value.func)
                    # x = ClassName(...) binds x's receiver type (the
                    # CapWord convention is the signal; function calls
                    # stay untyped — strict dispatch never guesses)
                    if callee and callee.rsplit(".", 1)[-1][:1].isupper():
                        var_types.setdefault(node.targets[0].id, callee)
                stack.extend(ast.iter_child_nodes(node))
            return FuncEntry(
                qual, func.name, cls_name, func.lineno,
                isinstance(func, ast.AsyncFunctionDef), parent,
                any(jit_decorator(d) for d in func.decorator_list),
                calls, aug_attrs, var_types,
            )

        def walk(node: ast.AST, cls_name: str | None, parent: str | None) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    entry = extract_func(child, cls_name, parent)
                    idx.functions.setdefault(entry.qual, entry)
                    walk(child, cls_name, entry.qual)
                elif isinstance(child, ast.ClassDef):
                    bases = [dotted(b) for b in child.bases if dotted(b)]
                    methods = [
                        n.name for n in child.body
                        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                    ]
                    attrs: dict[str, str] = {}
                    for sub in ast.walk(child):
                        # self.<attr> = ClassName(...) typed-attr inference
                        if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                            t = sub.targets[0]
                            if (
                                isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"
                                and isinstance(sub.value, ast.Call)
                            ):
                                callee = dotted(sub.value.func)
                                if callee and callee.rsplit(".", 1)[-1][:1].isupper():
                                    attrs.setdefault(t.attr, callee)
                        elif isinstance(sub, ast.AnnAssign) and isinstance(
                            sub.target, ast.Name
                        ):
                            ann = dotted(sub.annotation)
                            if ann:
                                attrs.setdefault(sub.target.id, ann)
                    idx.classes[child.name] = {
                        "bases": bases, "methods": methods, "attrs": attrs,
                    }
                    walk(child, child.name, None)
                else:
                    walk(child, cls_name, parent)

        walk(tree, None, None)

        # jit wrap sites anywhere (assignments keep their target name)
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                rec = self._jit_wrap_record(
                    node.value,
                    dotted(node.targets[0]) if len(node.targets) == 1 else "",
                )
                if rec is not None:
                    self.jit_wraps.append(rec)
                    if rec["target"]:
                        self.jit_assign_targets.append(rec["target"])
            elif isinstance(node, ast.Call):
                rec = self._jit_wrap_record(node, "")
                if rec is not None and not any(
                    w["lineno"] == rec["lineno"] and w["wrapped"] == rec["wrapped"]
                    for w in self.jit_wraps
                ):
                    self.jit_wraps.append(rec)

    # ------------------------------------------------------------- (de)ser
    def to_json(self) -> dict:
        return {
            "functions": {q: f.to_json() for q, f in self.functions.items()},
            "classes": self.classes,
            "imports": self.imports,
            "jit_wraps": self.jit_wraps,
            "jit_assign_targets": self.jit_assign_targets,
            "pspec_names": self.pspec_names,
            "str_tuples": self.str_tuples,
        }

    @classmethod
    def from_json(cls, path: str, d: dict) -> "ModuleIndex":
        idx = cls(path)
        idx.functions = {
            q: FuncEntry.from_json(f) for q, f in d["functions"].items()
        }
        idx.classes = d["classes"]
        idx.imports = d["imports"]
        idx.jit_wraps = d["jit_wraps"]
        idx.jit_assign_targets = d["jit_assign_targets"]
        idx.pspec_names = d["pspec_names"]
        idx.str_tuples = d["str_tuples"]
        return idx


def _module_dotted(relpath: str) -> str:
    """'k8s_llm_scheduler_tpu/engine/engine.py' -> dotted module path."""
    p = relpath[:-3] if relpath.endswith(".py") else relpath
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


class RepoGraph:
    """The merged whole-repo view + call resolution + reachability."""

    def __init__(self) -> None:
        self.modules: dict[str, ModuleIndex] = {}      # relpath -> index
        self.by_module_dotted: dict[str, str] = {}     # dotted -> relpath
        self.funcs: dict[str, FuncEntry] = {}          # gqual -> entry
        self.func_module: dict[str, str] = {}          # gqual -> relpath
        self.by_bare: dict[str, list[str]] = {}        # bare -> [gqual]
        self.class_module: dict[str, list[str]] = {}   # class -> [relpath]
        # build stats for the cache test + `--stats`-style introspection
        self.indexed_files: list[str] = []             # re-parsed this build
        self.cached_files: list[str] = []              # served from cache
        self._edges_memo: dict[tuple[str, str], tuple[str, ...]] = {}
        self._reach_memo: dict[tuple[frozenset[str], str], frozenset[str]] = {}

    # ------------------------------------------------------------- build
    @classmethod
    def build(
        cls,
        files: Iterable[Path],
        root: Path,
        cache_path: Path | None = None,
    ) -> "RepoGraph":
        graph = cls()
        cache: dict = {}
        if cache_path is not None and cache_path.is_file():
            try:
                loaded = json.loads(cache_path.read_text())
                if loaded.get("version") == INDEX_VERSION:
                    cache = loaded.get("modules", {})
            except (OSError, ValueError):
                cache = {}
        fresh: dict[str, dict] = {}
        dirty = False
        for path in files:
            try:
                rel = str(path.resolve().relative_to(root))
            except ValueError:
                rel = str(path)
            try:
                text = path.read_text()
            except OSError:
                continue
            sha = hashlib.sha256(text.encode()).hexdigest()
            entry = cache.get(rel)
            if entry is not None and entry.get("sha") == sha:
                idx = ModuleIndex.from_json(rel, entry["index"])
                graph.cached_files.append(rel)
                fresh[rel] = entry
            else:
                try:
                    tree = ast.parse(text)
                except SyntaxError:
                    continue  # the runner reports parse errors itself
                idx = ModuleIndex.build(rel, tree)
                graph.indexed_files.append(rel)
                fresh[rel] = {"sha": sha, "index": idx.to_json()}
                dirty = True
            graph._add(idx)
        if cache_path is not None and (dirty or set(fresh) != set(cache)):
            graph._write_cache(cache_path, fresh)
        graph._finish()
        return graph

    @classmethod
    def from_texts(cls, texts: dict[str, str]) -> "RepoGraph":
        """In-memory build (lint_text / fixture snippets)."""
        graph = cls()
        for name, text in texts.items():
            try:
                tree = ast.parse(text)
            except SyntaxError:
                continue
            graph._add(ModuleIndex.build(name, tree))
            graph.indexed_files.append(name)
        graph._finish()
        return graph

    @staticmethod
    def _write_cache(cache_path: Path, modules: dict) -> None:
        payload = json.dumps(
            {"version": INDEX_VERSION, "modules": modules},
            sort_keys=True, separators=(",", ":"),
        )
        tmp = cache_path.with_name(cache_path.name + f".tmp{os.getpid()}")
        try:
            tmp.write_text(payload)
            os.replace(tmp, cache_path)  # graftlint: ok[rename-without-fsync] — disposable derived cache; a torn file fails the version check and rebuilds
        except OSError:
            # a read-only checkout must still lint; the cache is an
            # optimization, never a requirement
            try:
                tmp.unlink()
            except OSError:
                pass

    def _add(self, idx: ModuleIndex) -> None:
        self.modules[idx.path] = idx
        self.by_module_dotted[_module_dotted(idx.path)] = idx.path
        for qual, entry in idx.functions.items():
            g = f"{idx.path}::{qual}"
            self.funcs[g] = entry
            self.func_module[g] = idx.path
            self.by_bare.setdefault(entry.name, []).append(g)
        for cname in idx.classes:
            self.class_module.setdefault(cname, []).append(idx.path)

    def _finish(self) -> None:
        # deterministic iteration everywhere downstream
        for quals in self.by_bare.values():
            quals.sort()

    # -------------------------------------------------------- jit roots
    def jit_roots(self) -> frozenset[str]:
        """Every function that is a jit/shard_map root: decorated defs,
        wrapped names (strict resolution into the defining module via
        imports), and the bare-name fallback the engine's cross-module
        jit idiom needs (engine/engine.py jits models/llama.py defs that
        ride in through locals the AST can't type)."""
        memo = getattr(self, "_jit_roots", None)
        if memo is not None:
            return memo
        roots: set[str] = set()
        wrapped_bares: set[str] = set()
        for rel, idx in self.modules.items():
            for qual, entry in idx.functions.items():
                if entry.jit_decorated:
                    roots.add(f"{rel}::{qual}")
            for wrap in idx.jit_wraps:
                wrapped_bares.add(wrap["wrapped"])
        for bare in wrapped_bares:
            roots.update(self.by_bare.get(bare, ()))
        self._jit_roots = frozenset(roots)
        return self._jit_roots

    # -------------------------------------------------------- resolution
    def _resolve_import(self, module_rel: str, name: str) -> list[str]:
        """Follow `name` through `module_rel`'s import table to defs."""
        idx = self.modules.get(module_rel)
        if idx is None:
            return []
        src = idx.imports.get(name)
        if not src:
            return []
        # src is "pkg.mod.symbol" or "pkg.mod"
        for cut in (src.rsplit(".", 1), (src, "")):
            mod_dotted, sym = cut if len(cut) == 2 else (cut[0], "")
            rel = self.by_module_dotted.get(mod_dotted)
            if rel is None:
                continue
            if sym:
                g = f"{rel}::{sym}"
                if g in self.funcs:
                    return [g]
                # imported class: constructor edge to __init__
                if sym in self.modules[rel].classes:
                    g = f"{rel}::{sym}.__init__"
                    return [g] if g in self.funcs else []
            return []
        return []

    def _class_method(self, cls_name: str, meth: str, home: str) -> list[str]:
        """`cls_name.meth` resolved in `home`'s import scope, walking
        base classes (by name) when the class itself lacks the method."""
        seen: set[str] = set()
        stack = [(cls_name, home)]
        while stack:
            cname, mod = stack.pop()
            cname = cname.rsplit(".", 1)[-1]
            if cname in seen:
                continue
            seen.add(cname)
            # resolve the class to its defining module(s)
            rels: list[str] = []
            idx = self.modules.get(mod)
            if idx is not None and cname in idx.classes:
                rels = [mod]
            elif idx is not None and cname in idx.imports:
                src = idx.imports[cname]
                mod_dotted, _, sym = src.rpartition(".")
                rel = self.by_module_dotted.get(mod_dotted)
                if rel is not None and sym in self.modules[rel].classes:
                    rels = [rel]
            else:
                rels = [
                    r for r in self.class_module.get(cname, [])
                ]
            for rel in rels:
                cinfo = self.modules[rel].classes.get(cname)
                if cinfo is None:
                    continue
                if meth in cinfo["methods"]:
                    g = f"{rel}::{cname}.{meth}"
                    if g in self.funcs:
                        return [g]
                for base in cinfo["bases"]:
                    stack.append((base, rel))
        return []

    def resolve_call(
        self, caller: str, callname: str, dispatch: str = "strict"
    ) -> list[str]:
        """Callee gquals for a `callname` call site inside `caller`."""
        rel = self.func_module.get(caller)
        if rel is None:
            return []
        entry = self.funcs[caller]
        idx = self.modules[rel]
        head, _, rest = callname.partition(".")

        if not rest:
            # bare call: enclosing-scope nested def, same-module def,
            # then the import table
            if entry.parent is not None:
                g = f"{rel}::{entry.parent}.<locals>.{callname}"
                if g in self.funcs:
                    return [g]
            for pref in (entry.qual + ".<locals>.",):
                g = f"{rel}::{pref}{callname}"
                if g in self.funcs:
                    return [g]
            g = f"{rel}::{callname}"
            if g in self.funcs:
                return [g]
            if callname in idx.classes:
                g = f"{rel}::{callname}.__init__"
                return [g] if g in self.funcs else []
            return self._resolve_import(rel, callname)

        meth = callname.rsplit(".", 1)[-1]
        if head in ("self", "cls") and entry.cls is not None:
            if "." not in rest:  # self.meth()
                hit = self._class_method(entry.cls, meth, rel)
                if hit:
                    return hit
            else:
                # self.attr.meth(): typed attribute inference
                attr = rest.rsplit(".", 1)[0]
                if "." not in attr:
                    cinfo = idx.classes.get(entry.cls, {})
                    atype = cinfo.get("attrs", {}).get(attr)
                    if atype:
                        hit = self._class_method(atype, meth, rel)
                        if hit:
                            return hit
        elif "." not in rest:
            # x.meth(): local/param type binding, module alias, or class
            recv_type = entry.var_types.get(head)
            if recv_type:
                hit = self._class_method(recv_type, meth, rel)
                if hit:
                    return hit
            if head in idx.classes:
                hit = self._class_method(head, meth, rel)
                if hit:
                    return hit
            src = idx.imports.get(head)
            if src:
                mod_rel = self.by_module_dotted.get(src)
                if mod_rel is not None:  # module alias: mod.fn()
                    g = f"{mod_rel}::{meth}"
                    if g in self.funcs:
                        return [g]
                else:
                    # imported class used as receiver type namespace
                    mod_dotted, _, sym = src.rpartition(".")
                    rel2 = self.by_module_dotted.get(mod_dotted)
                    if rel2 is not None and sym in self.modules[rel2].classes:
                        hit = self._class_method(sym, meth, rel2)
                        if hit:
                            return hit
        if dispatch == "bare" and meth not in _BARE_DISPATCH_BLOCKLIST:
            return list(self.by_bare.get(meth, []))
        return []

    # ------------------------------------------------------ reachability
    def edges(self, g: str, dispatch: str = "strict") -> tuple[str, ...]:
        key = (g, dispatch)
        memo = self._edges_memo.get(key)
        if memo is not None:
            return memo
        entry = self.funcs.get(g)
        out: list[str] = []
        if entry is not None:
            seen: set[str] = set()
            for call in entry.calls:
                for callee in self.resolve_call(g, call["n"], dispatch):
                    if callee not in seen:
                        seen.add(callee)
                        out.append(callee)
            # a function lexically encloses its nested defs: treat the
            # closure as part of the enclosing protocol (install() runs
            # inside swap_to's contract)
            for gq, _e in self._children_of(g):
                if gq not in seen:
                    seen.add(gq)
                    out.append(gq)
        res = tuple(out)
        self._edges_memo[key] = res
        return res

    def _children_of(self, g: str) -> list[tuple[str, FuncEntry]]:
        memo = getattr(self, "_children_memo", None)
        if memo is None:
            memo = {}
            for gq, e in self.funcs.items():
                if e.parent is not None:
                    rel = self.func_module[gq]
                    pg = f"{rel}::{e.parent}"
                    memo.setdefault(pg, []).append((gq, e))
            self._children_memo = memo
        return memo.get(g, [])

    def reachable(
        self, seeds: Iterable[str], dispatch: str = "strict"
    ) -> frozenset[str]:
        key = (frozenset(seeds), dispatch)
        memo = self._reach_memo.get(key)
        if memo is not None:
            return memo
        seen: set[str] = set()
        stack = [s for s in key[0] if s in self.funcs]
        while stack:
            cur = stack.pop()
            if cur in seen:
                continue
            seen.add(cur)
            stack.extend(self.edges(cur, dispatch))
        out = frozenset(seen)
        self._reach_memo[key] = out
        return out

    def reaches(
        self,
        start: str,
        pred: Callable[[FuncEntry], bool],
        dispatch: str = "strict",
        include_enclosing: bool = False,
    ) -> bool:
        """From `start`, is a function whose entry satisfies `pred`
        reachable (including `start` itself)? With `include_enclosing`,
        the lexical parent chain joins the seed set — a nested def runs
        inside its enclosing function's protocol, so evidence there
        counts for the closure."""
        seeds = [start]
        if include_enclosing:
            g = start
            while True:
                e = self.funcs.get(g)
                if e is None or e.parent is None:
                    break
                g = f"{self.func_module[g]}::{e.parent}"
                seeds.append(g)
        for g in self.reachable(seeds, dispatch):
            e = self.funcs.get(g)
            if e is not None and pred(e):
                return True
        return False

    # ----------------------------------------------------------- helpers
    def functions_in(self, rel: str) -> list[str]:
        idx = self.modules.get(rel)
        if idx is None:
            return []
        return [f"{rel}::{q}" for q in idx.functions]

    def str_tuple(self, rel_suffix: str, name: str) -> list[str] | None:
        """A module-level string-tuple constant, looked up by module path
        suffix (so the table survives repo-root-relative vs absolute
        naming differences)."""
        for rel, idx in self.modules.items():
            if rel.endswith(rel_suffix) and name in idx.str_tuples:
                return idx.str_tuples[name]
        return None


def iter_file_funcs(
    tree: ast.Module,
) -> Iterator[tuple[str, ast.FunctionDef | ast.AsyncFunctionDef, str | None]]:
    """(local qual, def node, owning class) for every function in `tree`,
    using EXACTLY the indexer's qual-generation scheme so AST nodes in a
    live FileContext line up with FuncEntry records from a cached index."""

    def walk(
        node: ast.AST, cls_name: str | None, parent: str | None
    ) -> Iterator:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = (
                    child.name if cls_name is None
                    else f"{cls_name}.{child.name}"
                )
                if parent is not None:
                    qual = f"{parent}.<locals>.{child.name}"
                yield qual, child, cls_name
                yield from walk(child, cls_name, qual)
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, child.name, None)
            else:
                yield from walk(child, cls_name, parent)

    yield from walk(tree, None, None)
