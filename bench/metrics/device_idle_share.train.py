"""Share of the traced training window in which no operation ran on the
device, in %: 1 minus the union of device-operation intervals over the window
from the first ``bench.round`` span's start to the last one's end, averaged
over the chips."""


def read(ctx):
    if not ctx.trace:
        return None
    return 100.0 * ctx.trace["idle_share"]
