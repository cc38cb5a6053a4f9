"""Operations and bytes the algorithm needs, from shapes alone: the rules
every family's work counts (``benchmark/families/<family>.py``) keep to,
and the arithmetic they share.

Counted for what the mathematics requires, whatever implements it, so a
share of a peak computed from these cannot pass 100% and stays valid
when a kernel is replaced. ``model`` is a configuration file's ``model``
object. Matmul FLOPs are 2 per multiply-add; the embedding is a gather
and counts nothing; causal attention is counted once (each query against
the keys at or before it); recomputation counts nothing.
"""


def causal_pairs(seq: int) -> int:
    """(query, key) pairs of one causal sequence of ``seq`` tokens."""
    return seq * (seq + 1) // 2
