"""step_ms.decode — mean host-clock time of ``Engine.step()`` over the
window's steps that carry no prompt chunk (decode rows only). The step
is synchronous: it ends in the logits copy-back."""


def read(ctx):
    times = [s.t1 - s.t0 for s in ctx.window_steps
             if s.rows and all(q == 1 for _, q in s.rows)]
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
