"""round.other_ms_per_round: device milliseconds per sync round spent
outside the ``round_step`` megakernel (the op stream, classic's jnp
epilogue, metric reductions, copies): busy time per round minus the
kernel's."""


def read(ctx):
    dev = ctx["device"]
    s = dev.kernel_s(ctx["kernel"])
    if s <= 0:
        return None
    return (dev.busy_s - s) / ctx["rounds"] * 1e3
