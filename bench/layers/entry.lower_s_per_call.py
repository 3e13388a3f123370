"""entry.lower_s_per_call: seconds the traced ``simulate_store`` call
spent lowering its programs to MLIR and compiling them or loading them
from the compile cache (JAX's monitoring events of the entry's host
path)."""


def read(ctx):
    return ctx["lower_s"]
