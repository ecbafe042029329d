"""The Pallas fold's share of its roofline: the bytes every fold in the
traced window needs (rows * P * 4 + P * 4 + rows * 4, rows per chip)
over the chip's HBM bandwidth, divided by the device time of the
kernel's trace events, averaged over the cell's chips."""

# The fold kernel's own ops in the device trace: the custom call that
# ``kernels.ops.fedagg_op`` lowers to, named ``%fedagg_op.<n> = ...``.
KERNEL = r"^%fedagg_op(\.\d+)? = "


def read(ctx):
    kernel_s = ctx.trace.op_s(KERNEL, ctx.chips)
    rows = ctx.work.get("fold_rows", [])
    if kernel_s <= 0 or not rows:
        return None
    p = ctx.model["params"]
    need = sum(ctx.counts.fold_bytes(r, p) for r in rows)
    return 100.0 * need / ctx.peak["hbm_bytes_per_s"] / kernel_s
