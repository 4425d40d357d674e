"""On the chip: do the reference's seeded weights equal the program's own
init, leaf by leaf? (A diagnostic; the comparison that decides `correct`
does not depend on it being exact, only on it being close to a bf16 ulp.)

    python3 benchmark/tests/check_init.py internlm2_5-1_8b 12345
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from harness import seam
    from k8s_llm_scheduler_tpu.engine import local
    from k8s_llm_scheduler_tpu.models.configs import get_config

    conf = seam.load_config(BENCH / "configs" / f"{sys.argv[1]}.json")
    seed = int(sys.argv[2])
    model = seam.program(conf).register(conf)
    mine = seam.reference(conf).init_weights(conf, seed)
    theirs = local._init_params(seed % (2**31 - 1), get_config(model))
    flat_m = dict(jax.tree_util.tree_leaves_with_path(mine))
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(theirs):
        other = flat_m[path]
        diff = jnp.max(jnp.abs(leaf.astype(jnp.float32) - other.astype(jnp.float32)))
        out[jax.tree_util.keystr(path)] = [float(diff), int(jnp.sum(leaf != other))]
    print(json.dumps({"device": jax.devices()[0].device_kind, "max_abs_diff_and_unequal": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
