"""compiles_in_window — ``Engine.trace_count`` at the window's end minus
at its start: forward shapes first traced inside the window (each one a
compile, or a load from the persistent cache). Target 0."""


def read(ctx):
    return float(sum(s.traces for s in ctx.window_steps))
