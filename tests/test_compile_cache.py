"""The compile-cache resolver (utils/compile_cache.py): placed from outside
by JAX's own variable, else at a fixed path inside the checkout.

enable_persistent_compile_cache() returns early on the cpu backend, so these
exercise the RESOLVER and, for the "sets nothing in code" rule, the config
calls the enabler makes."""

import os
from pathlib import Path

import jax

from k8s_llm_scheduler_tpu.utils import compile_cache as cc

REPO = Path(__file__).resolve().parent.parent


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "outside"))
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append(name)
    )
    for path in ("auto", "/somewhere/else"):
        assert cc.resolve_compile_cache_dir(path) == str(tmp_path / "outside")
        assert cc.enable_persistent_compile_cache(path) == str(tmp_path / "outside")
    assert updates == []
    assert not (tmp_path / "outside").exists()  # JAX's to create, not ours


def test_unset_resolves_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    assert cc.resolve_compile_cache_dir("auto") == str(REPO / ".xla_cache")
    assert cc.resolve_compile_cache_dir() == str(REPO / ".xla_cache")
    assert cc.resolve_compile_cache_dir("/explicit/dir") == "/explicit/dir"
    assert ".xla_cache/" in (REPO / ".gitignore").read_text().split()


def test_same_path_from_any_cwd_and_pid(monkeypatch, tmp_path):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    here = cc.resolve_compile_cache_dir("auto")
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(os, "getpid", lambda: 4242)
    monkeypatch.setenv("HOME", str(tmp_path))
    assert cc.resolve_compile_cache_dir("auto") == here
    assert os.path.isabs(here) and str(tmp_path) not in here


def test_none_disables(monkeypatch, tmp_path):
    for env in (None, str(tmp_path)):
        if env is None:
            monkeypatch.delenv(cc.ENV_VAR, raising=False)
        else:
            monkeypatch.setenv(cc.ENV_VAR, env)
        for path in (None, ""):
            assert cc.resolve_compile_cache_dir(path) is None
            assert cc.enable_persistent_compile_cache(path) is None


def test_accelerator_backend_sets_the_resolved_dir(monkeypatch, tmp_path):
    """Off the cpu backend and with the variable unset, the enabler is the
    one place the directory is set."""
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    target = str(tmp_path / "cache")
    assert cc.enable_persistent_compile_cache(target) == target
    assert updates == [("jax_compilation_cache_dir", target)]
    assert (tmp_path / "cache").is_dir()
