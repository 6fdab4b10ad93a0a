"""Share of the roofline of the whole solve, in %: the compulsory bytes
of roofline.compulsory_bytes at peak HBM bandwidth, over the device's
busy time per solve in the traced window."""
from readers import smoother_roofline_share as read  # noqa: F401
