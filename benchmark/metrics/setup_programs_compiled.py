"""Programs built before the window that the persistent compile cache did not
serve: 0 on a warm run. Read from the program's set-up record
(`metrics/_setup.py`); None where the program keeps none."""

from metrics import _setup


def read(ctx):
    return _setup.read(ctx, "programs_compiled")
