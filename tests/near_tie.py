"""Comparing greedy token streams across a quantisation.

Two runs of the same arithmetic give the same tokens, and the tests hold
them to that. A run that rounds differently (int8 collectives, an int8 KV
pool) gives logits within a bound of the exact run's, so on random
weights its greedy stream may part from the exact one wherever the exact
model's two best logits lie closer than that bound, and from there on
the streams are different texts. What holds is: the streams agree up to
the first such position, and that position is a near-tie.
"""
import numpy as np

import paddle_tpu as paddle


def assert_same_until_near_tie(model, prompt, exact, other, rel):
    """``exact`` and ``other`` (generated tokens after ``prompt``) are
    equal, or at the first position where they differ the float32
    model's logits for the two tokens lie within ``rel`` of the largest
    logit's magnitude. Returns whether the streams were equal."""
    if exact == other:
        return True
    i = next((k for k, (a, b) in enumerate(zip(exact, other)) if a != b),
             None)
    assert i is not None, (
        f"one stream is a prefix of the other: {len(exact)} and "
        f"{len(other)} tokens")
    ids = np.concatenate([prompt, np.asarray(exact[:i], np.int32)])
    logits = np.asarray(model(paddle.to_tensor(
        ids[None].astype(np.int32))).numpy())[0, -1].astype(np.float64)
    gap = abs(logits[exact[i]] - logits[other[i]])
    bound = rel * np.abs(logits).max()
    assert gap <= bound, (
        f"streams part at token {i} ({exact[i]} against {other[i]}) where "
        f"the exact logits differ by {gap:.3g}, over the bound {bound:.3g}")
    return False
