"""Host seconds a finalize takes (s): from the poll that found the bucket
done (quiescent, or at its horizon) to the re-grid's fresh carry on the
device, the ``bench.regrid`` span, averaged over the window's finalizes.
Nothing to read where the window finalized no bucket."""


def read(ctx):
    spans = ctx["window"].regrid_s
    return sum(spans) / len(spans) if spans else None
