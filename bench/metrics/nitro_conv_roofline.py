"""The ``kernels/nitro_conv`` Pallas kernels' share of their roofline, in %:
the least time the chip could take for the conv forward layers' work in
the traced window, over the summed device time of the package's ops
(``bench.work.kernel_roofline_share``)."""

from bench import work

#: the HLO names of the package's Pallas calls in the trace
KERNELS = r"^stream_conv"


def read(run):
    return work.kernel_roofline_share(run, "conv", KERNELS)
