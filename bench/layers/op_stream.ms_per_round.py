"""op_stream.ms_per_round: device milliseconds per sync round of the ops
under the program's ``op_stream`` scope (the op stream, its cast and the
gate of quiet rounds), by each op's ``tf_op`` in the trace."""


def read(ctx):
    s = ctx["scope_s"]("op_stream")
    if s <= 0:
        return None
    return s / ctx["rounds"] * 1e3
