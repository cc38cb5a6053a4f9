"""Conv layers (parity: /root/reference/python/paddle/nn/layer/conv.py).
Weight layout [out_c, in_c/groups, *k], NCHW default — XLA transposes to
the TPU-preferred layout internally."""
from __future__ import annotations

import numpy as np

from .. import functional as F
from .. import initializer as I
from .layers import Layer

__all__ = ["Conv1D", "Conv2D", "Conv3D", "Conv1DTranspose", "Conv2DTranspose",
           "Conv3DTranspose", "GatedShortConv"]


def _t(v, n):
    return (v,) * n if isinstance(v, int) else tuple(v)


class _ConvNd(Layer):
    def __init__(self, n, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, padding_mode="zeros",
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 transpose=False, output_padding=0):
        super().__init__()
        self._n = n
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = _t(kernel_size, n)
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.groups = groups
        self.data_format = data_format
        self.output_padding = output_padding
        self._transpose = transpose

        if transpose:
            w_shape = (in_channels, out_channels // groups) + self.kernel_size
        else:
            w_shape = (out_channels, in_channels // groups) + self.kernel_size
        fan_in = in_channels // groups * int(np.prod(self.kernel_size))
        bound = 1.0 / np.sqrt(fan_in)
        self.weight = self.create_parameter(
            w_shape, attr=weight_attr,
            default_initializer=(getattr(weight_attr, "initializer", None)
                                 or I.KaimingUniform(nonlinearity="leaky_relu")))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                (out_channels,), attr=bias_attr, is_bias=True,
                default_initializer=(getattr(bias_attr, "initializer", None)
                                     if bias_attr is not None else None)
                or I.Uniform(-bound, bound))

    def extra_repr(self):
        return (f"{self.in_channels}, {self.out_channels}, "
                f"kernel_size={self.kernel_size}, stride={self.stride}")


class Conv1D(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, padding_mode="zeros",
                 weight_attr=None, bias_attr=None, data_format="NCL"):
        super().__init__(1, in_channels, out_channels, kernel_size, stride,
                         padding, dilation, groups, padding_mode, weight_attr,
                         bias_attr, data_format)

    def forward(self, x):
        return F.conv1d(x, self.weight, self.bias, self.stride, self.padding,
                        self.dilation, self.groups, self.data_format)


class Conv2D(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, padding_mode="zeros",
                 weight_attr=None, bias_attr=None, data_format="NCHW"):
        super().__init__(2, in_channels, out_channels, kernel_size, stride,
                         padding, dilation, groups, padding_mode, weight_attr,
                         bias_attr, data_format)

    def forward(self, x):
        return F.conv2d(x, self.weight, self.bias, self.stride, self.padding,
                        self.dilation, self.groups, self.data_format)


class Conv3D(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, padding_mode="zeros",
                 weight_attr=None, bias_attr=None, data_format="NCDHW"):
        super().__init__(3, in_channels, out_channels, kernel_size, stride,
                         padding, dilation, groups, padding_mode, weight_attr,
                         bias_attr, data_format)

    def forward(self, x):
        return F.conv3d(x, self.weight, self.bias, self.stride, self.padding,
                        self.dilation, self.groups, self.data_format)


class Conv1DTranspose(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, output_padding=0, groups=1, dilation=1,
                 weight_attr=None, bias_attr=None, data_format="NCL"):
        super().__init__(1, in_channels, out_channels, kernel_size, stride,
                         padding, dilation, groups, "zeros", weight_attr,
                         bias_attr, data_format, transpose=True,
                         output_padding=output_padding)

    def forward(self, x, output_size=None):
        return F.conv1d_transpose(x, self.weight, self.bias, self.stride,
                                  self.padding, self.output_padding,
                                  self.groups, self.dilation, output_size,
                                  self.data_format)


class Conv2DTranspose(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, output_padding=0, groups=1, dilation=1,
                 weight_attr=None, bias_attr=None, data_format="NCHW"):
        super().__init__(2, in_channels, out_channels, kernel_size, stride,
                         padding, dilation, groups, "zeros", weight_attr,
                         bias_attr, data_format, transpose=True,
                         output_padding=output_padding)

    def forward(self, x, output_size=None):
        return F.conv2d_transpose(x, self.weight, self.bias, self.stride,
                                  self.padding, self.output_padding,
                                  self.groups, self.dilation, output_size,
                                  self.data_format)


class Conv3DTranspose(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, output_padding=0, groups=1, dilation=1,
                 weight_attr=None, bias_attr=None, data_format="NCDHW"):
        super().__init__(3, in_channels, out_channels, kernel_size, stride,
                         padding, dilation, groups, "zeros", weight_attr,
                         bias_attr, data_format, transpose=True,
                         output_padding=output_padding)

    def forward(self, x, output_size=None):
        return F.conv3d_transpose(x, self.weight, self.bias, self.stride,
                                  self.padding, self.output_padding,
                                  self.groups, self.dilation, output_size,
                                  self.data_format)


class GatedShortConv(Layer):
    """The gated short convolution of the LFM2 models over x
    [batch, seq, hidden]: ``[B, C, X] = in_proj(x)``, a depthwise causal
    convolution of ``kernel_size`` taps over ``B * X`` along the sequence
    (zeros before each sequence's first token), gated by ``C``, then
    ``out_proj``; no biases (``ops.short_conv``). ``conv_weight`` is
    [kernel_size, hidden]: tap j weighs the token ``kernel_size - 1 - j``
    places back."""

    def __init__(self, hidden_size: int, kernel_size: int = 3, dtype=None):
        super().__init__(dtype=dtype)
        from .common import Linear
        self.in_proj = Linear(hidden_size, 3 * hidden_size, bias_attr=False)
        self.conv_weight = self.create_parameter((kernel_size, hidden_size))
        self.out_proj = Linear(hidden_size, hidden_size, bias_attr=False)

    def forward(self, x):
        from ...framework.core import apply
        from ...ops.short_conv import gated_short_conv
        from ...utils import telemetry
        telemetry.default_tracer().metrics.inc("short_conv.layers")
        return apply("gated_short_conv", gated_short_conv, x,
                     self.in_proj.weight, self.conv_weight,
                     self.out_proj.weight)
