"""Device ms per solve of the linearisations: the leaf ops in the
program's ``nonlinear.linearize`` scope (every pass)."""
from layers import scope_ms


def read(ctx):
    return scope_ms(ctx, "nonlinear.linearize")
