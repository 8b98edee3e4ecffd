def read(ctx, params):
    """The whole step's share of the chip's peak, in %: model FLOPs per item
    x items/s/chip / peak.  Nothing without a table of peaks (no TPU)."""
    if ctx["peaks"] is None:
        return None
    flops = ctx["manifest"].function(
        "opcounts", params["flops_per_item"])(ctx["model"], ctx["mix"])
    return 100.0 * flops * ctx["items_per_s_per_chip"] \
        / ctx["peaks"][params["peak"]]
