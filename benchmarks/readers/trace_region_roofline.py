import sys

from benchmarks.harness import regions


def read(ctx, params):
    """As `trace_kernel_roofline`, the work found by region instead of by
    kernel name: the least time the chip could take for the work (the larger
    of operations / peak FLOP/s and bytes / peak B/s) over the device time
    per step in the named buckets, in %.  Nothing where the region table is
    not there or the buckets are empty: never 0."""
    t = regions.of(ctx)
    if t is None or ctx["peaks"] is None:
        return None
    ms = regions.ms_per_step(t, params["regions"])
    if not ms:
        return None
    cost = ctx["manifest"].function(
        "opcounts", params["cost"])(ctx["model"], ctx["mix"])
    by_ops = cost["ops"] / ctx["peaks"][params["flops_peak"]] * 1e3
    by_bytes = cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"] * 1e3
    print(f"roofline {'+'.join(params['regions'])}: {ms:.3f} ms per step on "
          f"the device; least by operations {by_ops:.3f} ms, by bytes "
          f"{by_bytes:.3f} ms: {'compute' if by_ops >= by_bytes else 'memory'}"
          f"-bound", file=sys.stderr)
    return 100.0 * max(by_ops, by_bytes) / ms
