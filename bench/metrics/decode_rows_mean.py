"""decode_rows_mean — decode rows in the batch, read from the engine's
scheduler before each step of the window, averaged over the steps."""


def read(ctx):
    rows = [s.decode_rows for s in ctx.window_steps]
    if not rows:
        return None
    return sum(rows) / len(rows)
