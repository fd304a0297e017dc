"""The rise of some /metrics counters over the window, summed, over the
rise of others: ``scale x sum(delta[num]) / sum(delta[den])``. A series
the server does not export (an older program) or a denominator that did
not move gives None.
selector: {"num": [series, ...], "den": [series, ...], "scale": 100}"""


def reduce(ctx, selector):
    delta = ctx["delta"]
    num, den = selector["num"], selector["den"]
    if any(s not in delta for s in num + den):
        return None
    below = sum(delta[s] for s in den)
    if not below:
        return None
    return selector.get("scale", 1.0) * sum(delta[s] for s in num) / below
