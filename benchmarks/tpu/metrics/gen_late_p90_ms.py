"""Load generator: how late requests were submitted, 90th percentile (ms).

Submit time minus due time, over the window's requests.  The server's
pump holds the event loop for a whole scheduler tick, so a request that
falls due during a tick is submitted when the tick ends.
"""
import numpy as np


def read(ctx):
    win = ctx.out.win
    late = [(r.submitted - win.t0 - r.req.due) * 1e3
            for r in win.records if r.submitted is not None]
    return float(np.percentile(late, 90)) if late else None
