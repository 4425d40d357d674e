"""`moe_grouped_matmul`'s share of its roofline at the shortcut-connected
family's widths (ops/grouped_matmul.py: down of the touched experts held
here, [2048, 6144], float32 out), from the device trace and the wave's own
counters (metrics/_moe.py; cost function arch/mla_scmoe.py
`grouped_kernel_cost`). Byte-bound (kernels)."""

from metrics import _moe


def read(ctx):
    return _moe.kernel_roofline(ctx, "moe_grouped_matmul", ctx.conf["expert_ffn_hidden_size"],
                                ctx.conf["hidden_size"], 1, 4)
