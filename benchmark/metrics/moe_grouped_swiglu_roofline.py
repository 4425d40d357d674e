"""`moe_grouped_swiglu`'s share of its roofline (ops/grouped_matmul.py:
gate and up of the touched experts, [2048, 1536] each, bf16 out), from the
device trace and the wave's own counters (metrics/_moe.py). The byte bound
holds: a few dozen rows against up to 64 experts' weights (kernels)."""

from metrics import _moe


def read(ctx):
    return _moe.kernel_roofline(ctx, "moe_grouped_swiglu", ctx.conf["hidden_size"],
                                ctx.conf["moe_intermediate_size"], 2, 2)
