"""Device ms per solve of the log-depth scan and its combines: the leaf
ops in the program's ``smoother.scan`` scope."""
from layers import scope_ms


def read(ctx):
    return scope_ms(ctx, "smoother.scan")
