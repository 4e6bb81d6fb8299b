"""Kernels: the SID-dispatched traverse kernel (dt_traverse_pallas).  The
least time the hops' bytes and the subtree tables need at the chip's HBM
bandwidth (``roofline.traverse_bytes`` + tables), over the kernel's
device time, in %."""
KERNEL = r"dt_traverse"


def read(ctx):
    t, peak = ctx["trace"], ctx["peak"]
    if t is None or peak is None:
        return None
    s = t.op_seconds(KERNEL)
    b = ctx["bytes"]["traverse"] + ctx["bytes"]["tables"]
    if s <= 0 or b <= 0:
        return None
    return 100.0 * b / peak["hbm_bytes_per_s"] / s
