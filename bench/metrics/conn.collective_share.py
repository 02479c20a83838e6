"""Share of a chip's busy time spent in collectives (all-gather,
all-reduce and the like), the largest over the chips used (%).  Nothing to
read where no collective ran."""


def read(ctx):
    shares = [
        d.collective_ns / d.busy_ns
        for d in ctx["trace"].devices.values() if d.busy_ns > 0
    ]
    if not shares or max(shares) <= 0:
        return None
    return 100.0 * max(shares)
