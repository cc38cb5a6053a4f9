"""A number out of ``ServingEngine.stats()`` as it stood when the window
closed (the counters are cleared when it opens): ``key`` alone, or the
ratio ``num`` / ``den`` of two keys, times ``scale``."""


def read(ctx, key=None, num=None, den=None, scale=1.0):
    stats = ctx["stats"]
    if key is not None:
        value = stats.get(key)
        return None if value is None else scale * value
    if not stats.get(den):
        return None
    return scale * stats[num] / stats[den]
