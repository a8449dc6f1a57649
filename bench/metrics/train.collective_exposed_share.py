"""Share of the traced training window in which a collective-permute (the
gossip exchange) ran on a chip with no computation running there, in %,
averaged over the chips. Found by the operation's category in the trace;
other collectives (the loss's all-reduce) are not counted. Silent where the
trace holds no collective-permute."""


def read(ctx):
    if not ctx.trace or ctx.trace["collective_exposed_share"] is None:
        return None
    return 100.0 * ctx.trace["collective_exposed_share"]
