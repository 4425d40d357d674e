"""The program's whole build, `engine.setup_build`: tokenizer, mesh, weights,
engine, backend (s). Read from the program's set-up record
(`metrics/_setup.py`); None where the program keeps none."""

from metrics import _setup


def read(ctx):
    return _setup.read(ctx, "build_s")
