"""Device time of the operations whose trace name matches a pattern, as
a share (%) of device busy time.
selector: {"pattern": "<regex on the op name the trace shows>"}"""

import re


def reduce(ctx, selector):
    dev = ctx["device"]
    if not dev or not dev["busy_s"]:
        return None
    pat = re.compile(selector["pattern"])
    t = sum(s for name, s in dev["op_seconds"] if pat.search(name))
    return 100.0 * t / dev["busy_s"]
