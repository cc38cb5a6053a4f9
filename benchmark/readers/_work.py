"""What the model was asked to do inside the traced window, for the
readers that set device time against a peak."""
from .. import costs


def traced_work(ctx) -> dict:
    """{"flops", "tokens", "pairs", "decode_pairs", "steps"} of the traced
    window: serving from the driver's token stamps, training from the
    steps the window span held."""
    res, model = ctx["res"], ctx["model"]
    t0, t1 = ctx["trace_clock"]
    if "recs" in res:
        from ..drivers._serving import work_counts
        w = work_counts(res["recs"], t0, t1)
        w["flops"] = costs.forward_flops(model, w["tokens"], w["pairs"])
        return w
    mix = ctx["mix"]
    steps = int(mix["trace_steps"])
    return {"steps": steps,
            "flops": steps * costs.train_flops(model, mix["batch"],
                                               mix["seq"]),
            "flash_flops": steps * costs.flash_train_flops(
                model, mix["batch"], mix["seq"])}
