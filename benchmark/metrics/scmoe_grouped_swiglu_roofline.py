"""`moe_grouped_swiglu`'s share of its roofline at the shortcut-connected
family's widths (ops/grouped_matmul.py: gate and up of the touched experts
held here, [6144, 2048] each, bf16 out), from the device trace and the
wave's own counters (metrics/_moe.py; cost function arch/mla_scmoe.py
`grouped_kernel_cost`). Byte-bound: a handful of rows against 50 MB of
weights an expert touched (kernels)."""

from metrics import _moe


def read(ctx):
    return _moe.kernel_roofline(ctx, "moe_grouped_swiglu", ctx.conf["hidden_size"],
                                ctx.conf["expert_ffn_hidden_size"], 2, 2)
