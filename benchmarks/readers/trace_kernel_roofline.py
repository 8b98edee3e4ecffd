import sys

from benchmarks.harness import xplane


def read(ctx, params):
    """The least time the chip could take for the work (the larger of
    operations / peak FLOP/s and bytes / peak B/s) over the kernels' device
    time per step, in %.  Says on stderr which bound holds.  Nothing where
    no event of those names ran: never 0."""
    if not ctx["trace"] or ctx["peaks"] is None:
        return None
    ms = xplane.kernel_ms_per_step(ctx["trace"], params["events"])
    if not ms:
        return None
    cost = ctx["manifest"].function(
        "opcounts", params["cost"])(ctx["model"], ctx["mix"])
    by_ops = cost["ops"] / ctx["peaks"][params["flops_peak"]] * 1e3
    by_bytes = cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"] * 1e3
    print(f"roofline {'/'.join(params['events'])}: {ms:.3f} ms per step on "
          f"the device; least by operations {by_ops:.3f} ms, by bytes "
          f"{by_bytes:.3f} ms: {'compute' if by_ops >= by_bytes else 'memory'}"
          f"-bound", file=sys.stderr)
    return 100.0 * max(by_ops, by_bytes) / ms
