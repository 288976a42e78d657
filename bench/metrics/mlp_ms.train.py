"""Device milliseconds a step in the operations under the ``mlp`` or
``moe`` scope, collectives left out (the sited helpers' chunked matmuls
are in), averaged over the cell's chips (``bench/scopes.py``)."""
from bench import scopes as S


def read(ctx):
    return S.part_ms(S.read(ctx), ("mlp", "moe"), collectives=False)
