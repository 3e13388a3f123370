"""round_step_roofline: the megakernel's share of its memory roofline, in
percent: the bytes one sync round must read and write (``roofline.py``,
from the store's shapes) over the chip's peak HBM bandwidth, divided by
the kernel's device time per round."""


def read(ctx):
    s = ctx["device"].kernel_s(ctx["kernel"])
    if s <= 0:
        return None
    least_s = ctx["round_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (s / ctx["rounds"])
