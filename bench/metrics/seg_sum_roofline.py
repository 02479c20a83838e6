"""``seg_sum``'s share of its roofline in the traced chunk (%): the least time
of its algorithmic work there (``bench/kernel_work.py``, counted from the
cell's shapes and load balancers) over the device time of its Mosaic ops.
Nothing to read where it did no counted work or did not run."""
from bench import kernel_work


def read(ctx):
    return kernel_work.roofline(ctx, "seg_sum")
