"""Share of the traced training window that the blocks' glue takes on
the device, in %: the summed time of the ops that the compiled step
places on a block's ``forward`` or ``backward`` scope, other than the
Pallas kernels (``layer_map.KERNELS``): limb pre-split, padding, slicing,
scaling, NITRO-ReLU, pooling, dropout and copies, over the window,
averaged over the chips."""

from bench import layer_map


def read(run):
    return layer_map.share(run, layer_map.is_block_glue)
