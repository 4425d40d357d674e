"""The architecture seam: a configuration's file says what it is
(`"architecture": "<name>"`), and that name alone selects, each from a
directory of its own under benchmark/,

    arch/<name>.py        register(conf), flops_per_token, attention_flops
    reference/<name>.py   init_weights, wave_logits (modes "f32" and "int8")

arch/README.md is the contract. A later PR that brings a model of another
architecture adds the two files with its configuration and edits nothing
here. No default: a configuration without the key is an error that names
it.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]  # benchmark/; tests point it at a directory of their own
KEY = "architecture"
_loaded: dict[Path, object] = {}


def load_config(path) -> dict:
    """The configuration file as it is run; without the key the run stops
    with a message that names the file and the key."""
    conf = json.loads(Path(path).read_text())
    if KEY not in conf:
        raise SystemExit(f'{path}: no "{KEY}" key: a configuration names the file under '
                         f"benchmark/arch/ and benchmark/reference/ that it runs through")
    return conf


def _module(directory: str, conf: dict):
    if KEY not in conf:
        raise KeyError(f'configuration {conf.get("name")!r} has no "{KEY}" key')
    path = ROOT / directory / f"{conf[KEY]}.py"
    if path not in _loaded:
        if not path.exists():
            raise FileNotFoundError(f'configuration {conf.get("name")!r} is "{KEY}": "{conf[KEY]}", '
                                    f"and there is no {path}")
        spec = importlib.util.spec_from_file_location(f"bench_{directory}_{conf[KEY]}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def program(conf: dict):
    """arch/<architecture>.py: the program's side and the FLOP count."""
    return _module("arch", conf)


def reference(conf: dict):
    """reference/<architecture>.py: the plain reference and its control."""
    return _module("reference", conf)
