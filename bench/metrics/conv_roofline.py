"""Roofline share of the convolution Pallas kernels (forward, input and
weight gradient) in training: least time of the training images' conv
work over the summed device time of the conv calls outside the
evaluation program, over all chips."""
from kernel_share import roofline_share


def read(ctx):
    return roofline_share(ctx, "conv", ctx["family"].conv_work)
