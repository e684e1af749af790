from repro.kernels.nitro_conv.nitro_conv import (
    folds_taps,
    stream_conv,
    stream_conv_fwd,
    stream_conv_grad_w,
    stream_conv_grad_w_opt,
    stream_conv_grad_x,
)
from repro.kernels.nitro_conv.ops import (
    conv_grad_w,
    conv_grad_w_opt,
    conv_grad_x,
    fused_conv,
    fused_conv_fwd,
)
from repro.kernels.nitro_conv.ref import (
    stream_conv_fwd_ref,
    stream_conv_grad_w_ref,
    stream_conv_grad_x_ref,
    stream_conv_ref,
)

__all__ = [
    "conv_grad_w",
    "conv_grad_w_opt",
    "conv_grad_x",
    "folds_taps",
    "fused_conv",
    "fused_conv_fwd",
    "stream_conv",
    "stream_conv_fwd",
    "stream_conv_fwd_ref",
    "stream_conv_grad_w",
    "stream_conv_grad_w_opt",
    "stream_conv_grad_w_ref",
    "stream_conv_grad_x",
    "stream_conv_grad_x_ref",
    "stream_conv_ref",
]
