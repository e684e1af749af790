"""Share of the traced training window that the IntegerSGD update takes
on the device, in %: the summed time of the ops that the compiled step
places on its ``update`` scope (``bench/layer_map.py``), jnp fusions and
the fused kernel alike, over the window, averaged over the chips."""

from bench import layer_map


def read(run):
    return layer_map.share(run, lambda layer, op: layer == layer_map.UPDATE)
