"""`moe_grouped_matmul`'s share of its roofline (ops/grouped_matmul.py:
down of the touched experts, [1536, 2048], float32 out), from the device
trace and the wave's own counters (metrics/_moe.py). Byte-bound
(kernels)."""

from metrics import _moe


def read(ctx):
    return _moe.kernel_roofline(ctx, "moe_grouped_matmul", ctx.conf["moe_intermediate_size"],
                                ctx.conf["hidden_size"], 1, 4)
