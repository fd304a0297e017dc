"""A percentile of one span's duration over the window's requests.
selector: {"span": "engine.queue_wait", "p": 90}"""

from chipbench import stats


def reduce(ctx, selector):
    vals = ctx["spans"].get(selector["span"])
    return stats.percentile(vals, selector["p"]) if vals else None
