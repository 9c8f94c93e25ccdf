"""The RMSNorm forward kernel (kernels/rmsnorm) against its memory
roofline; its backward is plain XLA and not counted here."""

from roofline import roofline


def read(ctx):
    return roofline(ctx, "rmsnorm")
