"""round_step.ms_per_round: device milliseconds of the ``round_step``
megakernel per sync round of the traced call (sum of its trace events
over the rounds)."""


def read(ctx):
    s = ctx["device"].kernel_s(ctx["kernel"])
    if s <= 0:
        return None
    return s / ctx["rounds"] * 1e3
