"""Insert kernel: device time of the Mosaic ``sketch_insert_walk`` kernel
per launch, from the device trace. Every batch launches it, those that
span a subwindow boundary too; only a batch in which some shard row
re-claims a ring slot takes the scan instead and launches no kernel."""


def read(ctx):
    k = ctx.trace["kernels"].get("sketch_insert_walk")
    return k[0] / k[1] * 1e3 if k and k[1] else None
