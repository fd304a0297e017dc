"""Device busy time between the profile's first and last op per output
token that fell to that interval on the client (``tokens_in_window``:
each live stream's tokens spread evenly from its first to its last,
``stats.tokens_between``)."""


def reduce(ctx, selector):
    dev = ctx["device"]
    if not dev or not dev.get("tokens_in_window"):
        return None
    return 1e3 * dev["busy_s"] / dev["tokens_in_window"]
