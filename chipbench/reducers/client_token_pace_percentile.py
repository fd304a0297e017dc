"""A percentile over every output token of the window's requests of the
time the token took on the client's clock: tokens leave the engine in
groups (a decode window of several steps), so the tokens of one stream
that arrive within ``merge_ms`` of each other are one group, and each
token of a group took (time since the stream's previous group) / (tokens
in the group). The median is the pace of a plain decode step: a stall
or a fused step is one group among dozens and does not move it.
selector: {"p": 50, "merge_ms": 2}"""

from chipbench import stats


def reduce(ctx, selector):
    merge_s, paces = selector["merge_ms"] / 1e3, []
    for r in ctx["window"]:
        groups = []  # [arrival of the group's first token, tokens]
        for t in r.res.token_times:
            if groups and t - groups[-1][0] <= merge_s:
                groups[-1][1] += 1
            else:
                groups.append([t, 1])
        for (t0, _n0), (t1, n) in zip(groups, groups[1:]):
            paces.extend([(t1 - t0) * 1e3 / n] * n)
    return stats.percentile(paces, selector["p"]) if paces else None
