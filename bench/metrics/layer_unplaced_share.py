"""Share of the traced training window taken by device ops that lie on
no layer of the LES step, in %: ops that the compiled step's text does
not hold, or whose ``op_name`` path holds none of the step's scopes
(``bench/layer_map.py``), over the window, averaged over the chips.  A
change that drops a scope shows here first."""

from bench import layer_map


def read(run):
    return layer_map.share(run, lambda layer, op: layer is None)
