"""Mean share of the KV pool's blocks in use, sampled by the driver after
every engine step inside the window."""


def read(ctx):
    loop = ctx["res"].get("loop")
    if loop is None:
        return None
    w0, w1 = ctx["res"]["window"]
    inside = [u for t, u in loop.pool_samples if w0 <= t < w1]
    return 100.0 * sum(inside) / len(inside) if inside else None
