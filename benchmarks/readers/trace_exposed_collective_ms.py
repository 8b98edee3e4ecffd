from benchmarks.harness import xplane


def read(ctx, params):
    if not ctx["trace"]:
        return None
    return xplane.exposed_collective_ms_per_step(ctx["trace"])
