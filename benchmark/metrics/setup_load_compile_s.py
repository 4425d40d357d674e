"""The backend's compile, or on a persistent-cache hit the retrieval and load,
of every program built before the window (s). Read from the program's set-up
record (`metrics/_setup.py`); None where the program keeps none."""

from metrics import _setup


def read(ctx):
    return _setup.read(ctx, "load_compile_s")
