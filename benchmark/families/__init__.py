"""A model family is one module here, found by a configuration's
``"family"`` key (``manifest.family_of``). It is the only holder of what
the benchmark knows about one architecture; everything else under
``benchmark/`` reaches it through that key. What a family module has:

``leaf_shapes(model)``
    [(name, shape) or (name, shape, kind)] of every seeded leaf; ``kind``
    is ``normal`` / ``ones`` / ``zeros`` (``benchmark.weights``).
``build_trainable(cfg)``
    (model, {leaf name: parameter name}): the program's trainable model
    of this configuration, for ``systems.Trainer`` to seed and step.
``build_decoder(cfg, load, **decoder)``, where the family serves
    the program's decoder behind ``ServingEngine``, its weights taken
    through ``load(name, shape)``; keywords go over ``cfg["decoder"]``.
``REFERENCE``
    the name of its plain reference, ``benchmark/reference/<name>.py``
    (``benchmark.check`` states what that module has to have).
``train_flops(model, batch, seq)``, ``forward_flops(model, tokens, pairs)``
    model FLOPs of one training step and of one forward pass, by the
    rules of ``benchmark.costs``.
``KERNEL_WORK``
    {name: f(model, work)}: what one kernel has to do for the traced
    work of ``readers._work.traced_work``, in FLOPs or bytes; a metric
    file's ``work`` names an entry.

A family module imports the program inside its builders only, so that a
reference may take the leaf list from it.
"""
