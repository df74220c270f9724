"""paged_attention_roofline — the work-queue paged int4 attention
kernel's share of its roofline: the least time its calls in the window
could take (per call, the larger of operations over the bf16 peak and
bytes over HBM bandwidth, from ``bench/costs/paged_attention.py``) over
the kernel's device time in the trace."""


def read(ctx):
    return ctx.roofline("paged_attention")
