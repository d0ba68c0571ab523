"""Roofline share of the dense (fully connected) Pallas kernel in training:
the least time of the training images' dense work (the larger of FLOPs
over the bf16 peak and bytes over HBM bandwidth, from unpadded layer
shapes) over the summed device time of the dense calls outside the
evaluation program, over all chips."""
from kernel_share import roofline_share


def read(ctx):
    return roofline_share(ctx, "dense", ctx["family"].dense_work)
