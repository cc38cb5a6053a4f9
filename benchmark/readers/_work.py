"""What the model was asked to do inside the traced window, for the
readers that set device time against a peak. The counts of FLOPs and
bytes are the family's (``ctx["family"]``, ``benchmark/families``)."""


def traced_work(ctx) -> dict:
    """The traced window's work. Serving, from the driver's token stamps:
    {"tokens", "pairs", "decode_tokens", "decode_pairs"}. Training, the
    steps the window span held: {"steps", "batch", "seq"}."""
    res = ctx["res"]
    if "recs" in res:
        from ..drivers._serving import work_counts
        return work_counts(res["recs"], *ctx["trace_clock"])
    mix = ctx["mix"]
    return {"steps": int(mix["trace_steps"]), "batch": mix["batch"],
            "seq": mix["seq"]}


def model_flops(ctx) -> int:
    """Model FLOPs of the traced work, as the family counts them."""
    family, model, w = ctx["family"], ctx["model"], traced_work(ctx)
    if "steps" in w:
        return w["steps"] * family.train_flops(model, w["batch"], w["seq"])
    return family.forward_flops(model, w["tokens"], w["pairs"])
