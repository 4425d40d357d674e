"""Records the small device trace the reduction is checked against
(tests/data/small_trace.xplane.pb) and prints what it holds. Run on the chip:

    python3 benchmark/tests/record_fixture.py chiprun_out/fixture

Three jitted steps of known shape, each under a host span, with pauses
between them, so busy time, idle gaps and their attribution are all there.
"""

from __future__ import annotations

import glob
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from harness import xplane

    out = Path(sys.argv[1])
    shutil.rmtree(out, ignore_errors=True)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    step = jax.jit(lambda a: (a @ a) * 0.5 + 1.0)
    step(x).block_until_ready()
    jax.profiler.start_trace(str(out))
    for name, pause in (("submit_wave", 0.02), ("harvest_wave", 0.03), ("bind", 0.01)):
        with jax.profiler.TraceAnnotation(name):
            for _ in range(4):
                x = step(x)
            x.block_until_ready()
            time.sleep(pause)
        time.sleep(0.015)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(out))
    profile = xplane.load(path)
    planes = {p.name: {l.name: len(list(l.events)) for l in p.lines} for p in profile.planes}
    print(json.dumps({"file": path, "bytes": Path(path).stat().st_size, "planes": planes})[:6000])
    for p in profile.planes:
        if p.name.startswith("/device:TPU:0"):
            for l in p.lines:
                for ev in list(l.events)[:6]:
                    print(l.name, ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
    print(json.dumps(xplane.reduce(profile)))
    shutil.copy(path, out / "small_trace.xplane.pb")
    for f in glob.glob(str(out / "plugins")):
        shutil.rmtree(f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
