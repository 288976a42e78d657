"""Device milliseconds a step in the operations under the ``optimizer``
scope (clipping and the AdamW update), averaged over the cell's chips
(``bench/scopes.py``)."""
from bench import scopes as S


def read(ctx):
    return S.part_ms(S.read(ctx), ("optimizer",))
