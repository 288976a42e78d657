"""Device milliseconds a step in the operations under the ``embed`` scope
(the lookup and its gradient), averaged over the cell's chips
(``bench/scopes.py``)."""
from bench import scopes as S


def read(ctx):
    return S.part_ms(S.read(ctx), ("embed",))
