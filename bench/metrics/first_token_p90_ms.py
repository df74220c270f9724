"""first_token_p90_ms — time to first token as ``bench/e2e.py`` takes
it (from when a request was due to the client seeing its first token,
90th percentile over the requests due in the window; one that never
shows a token counts at the drain's end), read in the traced run. Kept
per layer: runs of one schedule spread too widely for an end-to-end
bound (PERF.md, section 2)."""

from bench import e2e as E


def read(ctx):
    ttft = E.ttft_s(ctx.records, ctx.w0, ctx.w1, ctx.t_end)
    if not ttft:
        return None
    return 1e3 * E.percentile(ttft, 90)
