"""Python tracing and lowering of every program built before the window, each
trace once however deeply nested; paid again on every warm start, since the
compile cache keys on the lowered module (s). Read from the program's set-up
record (`metrics/_setup.py`); None where the program keeps none."""

from metrics import _setup


def read(ctx):
    return _setup.read(ctx, "trace_lower_s")
