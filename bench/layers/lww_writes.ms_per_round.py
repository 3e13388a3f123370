"""lww_writes.ms_per_round: device milliseconds per sync round of the ops
under the program's ``lww_writes`` scope (the LWW write stream's scatter
of a round's writes into the delta planes, inside ``op_stream``), by each
op's ``tf_op`` in the trace."""


def read(ctx):
    s = ctx["scope_s"]("lww_writes")
    if s <= 0:
        return None
    return s / ctx["rounds"] * 1e3
