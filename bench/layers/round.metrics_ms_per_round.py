"""round.metrics_ms_per_round: device milliseconds per sync round of the
ops under the program's ``round_metrics`` scope (the round's metric
assembly and sums) or ``convergence`` scope (the replicas' agreement
test), by each op's ``tf_op`` in the trace."""


def read(ctx):
    s = ctx["scope_s"]("round_metrics", "convergence")
    if s <= 0:
        return None
    return s / ctx["rounds"] * 1e3
