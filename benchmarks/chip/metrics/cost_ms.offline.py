"""Device ms per solve of the OM cost and step norms that ``diagnostics``
adds: the leaf ops in the program's ``estimator.cost`` scope."""
from layers import scope_ms


def read(ctx):
    return scope_ms(ctx, "estimator.cost")
