from benchmarks.harness import xplane


def read(ctx, params):
    if not ctx["trace"]:
        return None
    return 100.0 * xplane.idle_share(ctx["trace"])
