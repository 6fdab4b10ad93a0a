"""Device ms per solve of the in-block work around the scan: the leaf ops
in ``smoother.elements``, ``smoother.fill`` and ``smoother.recover``."""
from layers import scope_ms


def read(ctx):
    return scope_ms(ctx, "smoother.elements", "smoother.fill",
                    "smoother.recover")
