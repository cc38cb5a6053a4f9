"""The ratio ``num`` / ``den`` of two counters of the program's
process-wide registry as it stands after the window (a subsystem that
publishes there when the registry is read keeps what it last published
once it is freed). Nothing where the program has no such counters."""
from . import _program


def read(ctx, num, den, scale=1.0):
    found = _program.counters((num, den))
    if not found.get(den) or num not in found:
        return None
    return scale * found[num] / found[den]
