"""Device busy time per simulated row-tick in the traced chunk (us): the
busy seconds of one chip (averaged over the chips used) over the row-ticks
that chunk advanced.  Every chip runs the whole tick, so this is what one
row-tick costs the chip."""


def read(ctx):
    traced = ctx["window"].traced
    row_ticks = traced["ticks"] * traced["rows"]
    return 1e6 * ctx["trace"].busy_s / row_ticks
