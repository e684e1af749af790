"""Pallas TPU kernels for the paper's compute hot-spots.

nitro_matmul/  fused int8 x int8 -> int32 matmul + NITRO scaling +
               NITRO-ReLU (one MXU+VPU pass; 5x less HBM traffic on the
               pre-activation tensor than the unfused reference), plus the
               true backward kernels nitro_matmul_grad_w / grad_x whose
               VMEM *prologue* applies the NITRO-ReLU derivative + STE to
               the incoming delta tiles before the gradient matmuls
nitro_conv/    streaming implicit-im2col conv: row bands DMA'd into a
               VMEM ring, patch blocks formed in-kernel (never the
               (N*H*W, K^2*C) HBM patch matrix; ~K^2 less input traffic),
               same scale/ReLU epilogue + optional fused 2x2 maxpool;
               conv fwd, training fwd (a, z*), and both conv gradients
               with the same fused ReLU-bwd delta prologue
integer_sgd/   fused IntegerSGD update (Algorithm 1; 3 HBM streams vs 5)
grad_ops.py    the unified backward dispatcher: linear_grads/conv_grads
               own the ReLU-bwd/STE step and fold it into the kernel
               prologues (z_star=None is the no-activation route: plain
               integer matmuls) — core.layers.{linear,conv}_backward and
               core.blocks.forward_layers_backward all route through it

Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper; interpret mode on CPU), ref.py (pure-jnp oracle).  Attention is
deliberately NOT a kernel: the roofline reads FLOPs from the compiled HLO
and custom calls are opaque to the cost model (models/attention.py).
"""
