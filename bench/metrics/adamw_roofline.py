"""The fused AdamW kernel (kernels/adamw) against its memory roofline."""

from roofline import roofline


def read(ctx):
    return roofline(ctx, "adamw")
