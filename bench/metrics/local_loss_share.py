"""Share of the traced training window that the learning layers take on
the device, in %: the summed time of the ops that the compiled step
places on any block's ``local_loss`` scope (learning layers, local
gradient and loss, their backward; ``bench/layer_map.py``), over the
window, averaged over the chips."""

from bench import layer_map


def read(run):
    return layer_map.share(
        run, lambda layer, op: layer is not None
        and layer.endswith("/local_loss"))
