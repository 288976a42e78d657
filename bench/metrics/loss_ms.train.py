"""Device milliseconds a step in the operations under the ``loss`` scope
(the head's matmul and the chunked cross-entropy, both directions),
averaged over the cell's chips (``bench/scopes.py``)."""
from bench import scopes as S


def read(ctx):
    return S.part_ms(S.read(ctx), ("loss",))
