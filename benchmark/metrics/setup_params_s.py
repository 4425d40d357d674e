"""From the weights' dispatch until they are resident, `engine.setup_params`,
inside the build (s). Read from the program's set-up record
(`metrics/_setup.py`); None where the program keeps none."""

from metrics import _setup


def read(ctx):
    return _setup.read(ctx, "params_s")
