"""Device milliseconds a step in the operations under the ``layer_params``
scope: the sited trunk's slices of each layer's weights out of the
stacked arrays, and any sum of the layers' gradients back into them that
XLA keeps as an operation of its own, averaged over the cell's chips
(``bench/scopes.py``); nothing on the scan trunk."""
from bench import scopes as S


def read(ctx):
    return S.part_ms(S.read(ctx), ("layer_params",))
