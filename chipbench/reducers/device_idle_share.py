"""1 - (union of device-op intervals / the window from a chip's first op
to its last), in %. The profiler's own edges are no part of it."""


def reduce(ctx, selector):
    dev = ctx["device"]
    if not dev or not dev["window_s"]:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
