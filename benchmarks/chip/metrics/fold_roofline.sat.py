"""Kernels: the fold/finalize kernel (feature_update_finalize_pallas).
The least time the folded packets' bytes need at the chip's HBM
bandwidth (``roofline.fold_bytes``), over the kernel's device time, in %."""
KERNEL = r"update_finalize"


def read(ctx):
    t, peak = ctx["trace"], ctx["peak"]
    if t is None or peak is None:
        return None
    s = t.op_seconds(KERNEL)
    if s <= 0 or ctx["bytes"]["fold"] <= 0:
        return None
    return 100.0 * ctx["bytes"]["fold"] / peak["hbm_bytes_per_s"] / s
