import sys


def read(ctx, params):
    """As `trace_region_roofline`, the work found by sub-scope
    (`trace_subscope_ms`): the least time the chip could take for the work
    (the larger of operations / peak FLOP/s and bytes / peak B/s) over the
    device time per step under `<region>/<sub>`, in %.  Nothing where the
    region table is not there or nothing ran under the sub-scope."""
    if ctx["peaks"] is None:
        return None
    ms = ctx["manifest"].module("readers", "trace_subscope_ms").ms_per_step(
        ctx, params["region"], params["sub"], params["subs"])
    if not ms:
        return None
    cost = ctx["manifest"].function(
        "opcounts", params["cost"])(ctx["model"], ctx["mix"])
    by_ops = cost["ops"] / ctx["peaks"][params["flops_peak"]] * 1e3
    by_bytes = cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"] * 1e3
    print(f"roofline {params['region']}/{params['sub']}: {ms:.3f} ms per "
          f"step on the device; least by operations {by_ops:.3f} ms, by "
          f"bytes {by_bytes:.3f} ms: "
          f"{'compute' if by_ops >= by_bytes else 'memory'}-bound",
          file=sys.stderr)
    return 100.0 * max(by_ops, by_bytes) / ms
