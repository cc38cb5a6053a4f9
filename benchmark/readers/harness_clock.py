"""A number the driver took on its own clock (``res["clock"]``)."""


def read(ctx, key):
    return ctx["clock"].get(key)
