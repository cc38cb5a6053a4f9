"""Seconds the program's set-up spent in one stage of compiling, from the
``compile.*`` events jax's own monitoring feeds into the program's
record: the ``self_s`` (nested events counted once) of the events named
in ``events`` that were stamped before the window opened. What compiles
after it (the reference) is left out. The parts, and the events named in
``log`` beside them (how much of the backend's time was loading from the
compile cache, how many requests hit it), go to the run's log."""
from . import _program


def read(ctx, events, log=()):
    opened = ctx["res"]["window"][0]
    total = {}
    for r in _program.records(ctx):
        if r.get("kind") == "event" and r["name"].startswith("compile.") \
                and r["ts"] < opened:
            # an event without seconds (a cache hit or request) counts 1
            total[r["name"]] = total.get(r["name"], 0.0) \
                + r["args"].get("self_s", 1.0)
    if not total:
        return None
    _program.log("before the window: " + ", ".join(
        f"{name} {total.get(name, 0.0):.3f}" for name in (*events, *log)))
    return sum(total.get(e, 0.0) for e in events)
