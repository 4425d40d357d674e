"""Device time of a profiler trace by named scope, by kernel and by program.

    python tools/trace_scopes.py <file.xplane.pb | capture directory> [--json] [--top N]

For an operator's capture of a running scheduler
(`observability/trace.py` `device_trace`, or any `jax.profiler` trace) as
much as for a builder's chip run: which step of which program the device's
time went to, under the names the program gives from inside (PERF.md §3
lists them). The reduction is `observability/scopes.py` `reduce_scopes`;
this prints it. Needs no accelerator and no TensorFlow.

A compile cache written before the scopes existed serves executables
without them: when no operation of a `wave` program run carries
`block_decode` this prints "not measured" for everything read from
scopes, never "100% unscoped".
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from k8s_llm_scheduler_tpu.observability.scopes import reduce_scopes  # noqa: E402


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise SystemExit(f"no .xplane.pb under {path}")
    return found[-1]


def render(r: dict) -> str:
    busy = r["busy_s"]

    def row(seconds: float, label: str) -> str:
        share = 100.0 * seconds / busy if busy else 0.0
        return f"  {seconds:10.6f} s  {share:6.2f}%  {label}"

    out = [
        f"{r['plane']}: busy {busy:.6f} s of a {r['window_s']:.6f} s trace "
        f"(idle {100.0 * (1 - busy / r['window_s']) if r['window_s'] else 0.0:.3f}%)",
        "", "by program (XLA Modules):",
    ]
    for name, rec in r["programs"].items():
        out.append(row(rec["seconds"], f"{name}  x{rec['runs']}  "
                       f"({1000.0 * rec['seconds'] / rec['runs']:.3f} ms a run)"))
    out += ["", "by kernel name:"]
    out += [row(s, name) for name, s in r["kernels"].items()] or ["  (no Mosaic kernel ran)"]
    if not r["measured"]:
        out += ["", "by scope: not measured (no operation of a `wave` program run carries "
                "`block_decode`: the executable was compiled before the scopes existed, "
                "or this trace holds no wave)"]
        return "\n".join(out)
    out += ["", "by scope, within each program:"]
    for program, per in r["scopes"].items():
        out.append(f" {program}:")
        out += [row(s, scope) for scope, s in per.items()]
    out += ["", "largest operations:"]
    out += [row(o["seconds"], f"{o['op']}  [{o['program']}: {o['scope'] or '(no scope)'}]")
            for o in r["ops"]]
    ns = r["no_scope"]
    out += ["", f"under no scope of the program: {ns['seconds']:.6f} s, "
            f"{100.0 * ns['share']:.2f}% of busy; largest:"]
    out += [row(o["seconds"], f"{o['op']}  [{o['program']}]") for o in ns["top"][:5]]
    bd = r["block_decode"]
    gap = 100.0 * (bd["scope_s"] / bd["while_s"] - 1.0) if bd["while_s"] else float("nan")
    out += ["", f"block_decode: {bd['scope_s']:.6f} s by scope, {bd['while_s']:.6f} s inside the "
            f"wave program's `while` ({gap:+.3f}%)"]
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb file, or a directory that holds one")
    ap.add_argument("--json", action="store_true", help="print the reduction as one JSON object")
    ap.add_argument("--top", type=int, default=15, help="operations to list")
    args = ap.parse_args()
    reduced = reduce_scopes(find_trace(args.trace), top=args.top)
    print(json.dumps(reduced) if args.json else render(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main())
