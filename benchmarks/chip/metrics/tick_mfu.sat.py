"""Whole serving step: the window's least bytes (``roofline.work_bytes``:
fold, traverse, admission, tables) over the traced seconds times the
chip's peak HBM bandwidth, in %.  The peak is bandwidth: a tree walk
compares and gathers, with no multiply-adds."""

def read(ctx):
    t, peak = ctx["trace"], ctx["peak"]
    if t is None or peak is None or t.window_s <= 0:
        return None
    b = ctx["bytes"]["total"]
    if b <= 0:
        return None
    return 100.0 * b / (t.window_s * peak["hbm_bytes_per_s"])
