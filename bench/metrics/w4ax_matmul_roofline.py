"""w4ax_matmul_roofline — the W4Ax GEMM's share of its roofline: the
least time its calls in the window could take (per call, the larger of
operations over the int8 peak and bytes over HBM bandwidth, from
``bench/costs/w4ax_matmul.py``) over the kernel's device time in the
trace."""


def read(ctx):
    return ctx.roofline("w4ax_matmul")
