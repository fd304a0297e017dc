"""A /metrics counter's rise over the window as a share (%) of the
prompt tokens of the window's requests.
selector: {"series": "engine_prefix_cache_hits_tokens"}"""


def reduce(ctx, selector):
    prompt = sum(r.req.prompt_tokens for r in ctx["window"])
    if selector["series"] not in ctx["delta"] or not prompt:
        return None
    return 100.0 * ctx["delta"][selector["series"]] / prompt
