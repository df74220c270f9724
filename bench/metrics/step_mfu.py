"""step_mfu — the whole model step's share of the chip's int8 peak over
the window: the operations the steps needed (2 per weight parameter per
token for the seven projections of every layer, 2 per head parameter per
row whose logits are read, and attention's QK and PV over each row's
history and the causal half of its chunk), divided by the window times
the int8 peak. The projections run int8 x int8 on the MXU, so the int8
peak is the bound."""


def _attention_ops(cfg, rows):
    hq, d = cfg["num_attention_heads"], cfg["head_dim"]
    return cfg["num_hidden_layers"] * sum(
        4.0 * hq * d * q * (c + (q + 1) / 2) for c, q in rows)


def read(ctx):
    cfg = ctx.cfg
    gemm = ctx.cost("w4ax_matmul")
    ops = sum(sum(o for o, _ in gemm.calls(cfg, s.rows))
              + _attention_ops(cfg, s.rows)
              + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * len(s.rows)
              for s in ctx.window_steps)
    if ops <= 0:
        return None
    return 100.0 * ops / ((ctx.w1 - ctx.w0) * ctx.peaks["int8_ops_s"])
