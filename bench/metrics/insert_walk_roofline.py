"""Insert kernel: share of the HBM roofline. The least bytes of one
launch (``rooflines.walk_bytes``: the touched key tiles read and written
once, the probe arrays read once), averaged over the batches of the
traced window (as far as the device recorded it) that lie in one
subwindow, at the peak bandwidth, over the kernel's mean device time per
launch in the trace. Batches that span a subwindow boundary launch the
kernel too but are left out of the average; their least bytes hardly
differ."""

import numpy as np


def read(ctx):
    from bench.rooflines import walk_bytes
    k = ctx.trace["kernels"].get("sketch_insert_walk")
    if not k or not k[1]:
        return None
    sk = ctx.sketch
    per_launch = []
    n = min(ctx.window["batches"], ctx.trace["host_n"].get("ingest", 0))
    for b in ctx.plan.batches[:n]:
        w = b["time"] // sk.sub
        if w[0] == w[-1]:
            per_launch.append(walk_bytes(sk, sk.block(b["src_label"]),
                                         sk.block(b["dst_label"])))
    if not per_launch:
        return None
    least_s = np.mean(per_launch) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (k[0] / k[1])
