"""One reduction shared by the metrics that read the scopes the program
gives its device work (`jax.named_scope`, PR 25): device seconds of the
traced slice by scope path, and how a reader asks for a scope.

It reads `ctx.xplane_path` (the `.xplane.pb` of the traced slice, which
run.py keeps until the last reader has returned), `ctx.cluster.acks` and
`ctx.outcome.trace_span`, and nothing of the program. On the first device
plane, line "XLA Ops" (harness/scopes.py reads the file):

- an operation's scope is the `tf_op` path of its event metadata less the
  compiler's structure: `jit(wave)/jit(main)/block_decode/while/body/
  model/mlp/dot_general:` -> `block_decode/model/mlp`;
- a `while` / `conditional` / `call` event spans its body, whose
  operations are events of their own: a wrapper's time is never summed.
  It gets the path that every scoped operation inside it shares, and an
  operation WITHOUT a scope of its own (a copy the compiler put in, a
  Mosaic kernel) takes the scope of the wrapper it runs inside;
- what then has no scope is counted under "(no scope)".

The same two passes as the program's observability/scopes.py
`reduce_scopes`, so a metric here and `tools/trace_scopes.py` on a kept
trace say the same seconds (tests/test_scope_trace.py holds both to one
recorded trace). The paths are summed over every program of the slice.

A reader asks by a scope's name, in one of two ways. `per_bind_ms(ctx,
"attn")` is the device time of the operations UNDER scope `attn`: every
path that has the component, so `block_decode/model/attn`,
`suffix_prefill/attn`, `prefix_prefill/attn` and what lies beneath them,
which is the attention kernels (a `pl.pallas_call`'s `name=` ends the path
of its own operation: `.../attn/flash_prefix_attention_parts`), per bind
acknowledged in the slice. `per_bind_ms(ctx, "model", own=True)` is the
time of the operations whose INNERMOST scope is `model`: what a scope does
itself, beside the scopes it holds. A model of another architecture names
its own scopes (an expert layer, a router, a short convolution) and its
readers ask for them the same way, in files of their own.

A program that names nothing, or executables from a compile cache older
than the scopes, leave every operation under "(no scope)": `reduced(ctx)`
is then None and every reader built on it returns None, never 0 and never
"100% unscoped".
"""

from __future__ import annotations

from harness import scopes, xplane

NO_SCOPE = "(no scope)"


def seconds_by_scope(path: str) -> dict | None:
    """{"busy_s", "scopes": {scope path: seconds}} of the first device
    plane; None where the file holds no device operation."""
    planes = scopes.read_device_planes(path)
    if not planes:
        return None
    plane = min(planes, key=lambda p: int(xplane.DEVICE_PLANE.match(p.name).group(1)))
    ops = plane.lines.get(xplane.OPS_LINE, [])
    if not ops:
        return None

    described: dict[int, tuple[bool, str]] = {}

    def describe(meta: int) -> tuple[bool, str]:
        """(is a wrapper, own scope), once per distinct operation."""
        if meta not in described:
            ev = plane.events[meta]
            kind = xplane.short_name(ev["name"]).split(" ")[0].split(".")[0]
            described[meta] = (kind in xplane.WRAPPERS,
                               scopes.scope_of(str(ev["stats"].get("tf_op", "")))[0])
        return described[meta]

    # Pass 1: a wrapper gets the path that every scoped operation inside it shares.
    shared: dict[int, list[str] | None] = {}
    open_wrappers: list[tuple[int, int]] = []  # (end_ps, index in ops)
    for i, (start, dur, meta) in enumerate(ops):
        while open_wrappers and open_wrappers[-1][0] <= start:
            open_wrappers.pop()
        is_wrapper, scope = describe(meta)
        if is_wrapper:
            open_wrappers.append((start + dur, i))
            shared[i] = None
        elif scope and open_wrappers:
            parts = scope.split("/")
            for _end, wi in open_wrappers:
                have = shared[wi]
                if have is None:
                    shared[wi] = parts
                else:
                    n = 0
                    while n < min(len(have), len(parts)) and have[n] == parts[n]:
                        n += 1
                    shared[wi] = have[:n]

    # Pass 2: seconds by scope; an operation without one takes its wrapper's.
    ps = 1e-12
    by_scope: dict[str, float] = {}
    wrappers: list[tuple[int, str]] = []  # (end_ps, scope) of the open wrapper events
    busy_end = total = 0
    for i, (start, dur, meta) in enumerate(ops):
        is_wrapper, scope = describe(meta)
        while wrappers and wrappers[-1][0] <= start:
            wrappers.pop()
        if is_wrapper:
            wrappers.append((start + dur, "/".join(shared[i] or ())))
            continue
        if not scope and wrappers:
            scope = wrappers[-1][1]
        if start + dur > busy_end:
            total += start + dur - max(start, busy_end)
            busy_end = start + dur
        by_scope[scope or NO_SCOPE] = by_scope.get(scope or NO_SCOPE, 0.0) + dur * ps
    return {"busy_s": total * ps, "scopes": by_scope}


def reduced(ctx) -> dict | None:
    """{"busy_s", "scopes", "binds"}, all times in seconds; None where
    there is no trace file or no operation under a scope of the program."""
    if "_scope_trace" in ctx.__dict__:
        return ctx._scope_trace
    ctx._scope_trace = out = _reduce(ctx)
    return out


def _reduce(ctx) -> dict | None:
    path = getattr(ctx, "xplane_path", None)
    if path is None or ctx.outcome.trace_span is None:
        return None
    out = seconds_by_scope(path)
    if out is None or not any(s != NO_SCOPE for s in out["scopes"]):
        return None
    ta, tb = ctx.outcome.trace_span
    out["binds"] = sum(1 for t in ctx.cluster.acks.values() if ta <= t < tb)
    return out


def seconds_under(ctx, name: str, own: bool = False) -> float | None:
    """Device seconds of the operations under scope `name` (a component
    of their path, at any depth), in every program of the slice; with
    `own`, of those whose innermost scope it is. None where no operation
    ran under it."""
    r = reduced(ctx)
    if r is None:
        return None
    found = [s for path, s in r["scopes"].items()
             if (path.rsplit("/", 1)[-1] == name if own else name in path.split("/"))]
    return sum(found) if found else None


def per_bind_ms(ctx, name: str, own: bool = False) -> float | None:
    """1000 x seconds_under(name) / binds acknowledged in the slice."""
    seconds = seconds_under(ctx, name, own)
    if seconds is None or not reduced(ctx)["binds"]:
        return None
    return 1000.0 * seconds / reduced(ctx)["binds"]
