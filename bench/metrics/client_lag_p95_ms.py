"""client_lag_p95_ms — how late the open-loop client sent requests: the
95th percentile, over the requests due in the window, of the client's
send time minus the request's due time (the client is single-threaded,
so a request that falls due during a step waits for the step)."""

import numpy as np


def read(ctx):
    lags = [r.sent - r.due for r in ctx.records
            if ctx.w0 <= r.due < ctx.w1 and ctx.loop == "open"]
    if not lags:
        return None
    return 1e3 * float(np.percentile(lags, 95))
