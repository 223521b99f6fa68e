"""Insert kernel: device time of the Mosaic ``sketch_insert_walk`` kernel
per launch, from the device trace. Batches that span a subwindow boundary
take the scan instead and launch no kernel."""


def read(ctx):
    k = ctx.trace["kernels"].get("sketch_insert_walk")
    return k[0] / k[1] * 1e3 if k and k[1] else None
