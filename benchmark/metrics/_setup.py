"""The program's record of its own set-up (PR 39): `get_stats()["setup"]`
of the engine's backend, as the benchmark's snapshot at the window's open
holds it (`ctx.outcome.before`). Every number there is cumulative since the
process began, so at the open it is set-up's alone: the build's two spans
(`engine.setup_build`, `engine.setup_params`) and the compile log's books
(`utils/compile_cache.py` `COMPILE_LOG`). A program without the record (a
parent of PR 39) reads None, not 0."""

PATH = ("sched", "client", "engine", "setup")


def read(ctx, key: str):
    record = ctx.outcome.before
    for part in PATH:
        record = record.get(part) if isinstance(record, dict) else None
    if not isinstance(record, dict) or key not in record:
        return None
    return float(record[key])
