"""Share of the device's busy time spent in the Mosaic tick kernels,
summed over the chips used (%).  Nothing to read where no kernel ran."""


def read(ctx):
    devs = ctx["trace"].devices.values()
    kernel = sum(d.kernel_ns for d in devs)
    busy = sum(d.busy_ns for d in devs)
    if kernel <= 0 or busy <= 0:
        return None
    return 100.0 * kernel / busy
