"""A percentile over the window's requests of each stream's time per
output token, (last token - first token) / (tokens - 1), on the client's
clock; a failed request ranks above every measured value, and a
percentile that reaches into them is left out.
selector: {"p": 90}"""

import math

from chipbench import stats


def reduce(ctx, selector):
    vals = [stats.tpot_ms(r.res.token_times)
            if r.res.error is None and (r.ok or r.cut) else stats.FAILED
            for r in ctx["window"]]
    if not vals:
        return None
    v = stats.percentile(vals, selector["p"])
    return v if math.isfinite(v) else None
