from benchmarks.harness import regions


def read(ctx, params):
    """Device time per step in the named buckets of `harness/regions.py`
    (forward and backward together), in ms, on the chip that is busy
    longest; with `"share": true`, that time as a share of the chip's busy
    time, in %.  Nothing without a trace, where the program has no region
    scopes, or where the rebuilt step's names cover under 99% of the traced
    time; nothing for a time where no operation of those buckets ran: never
    0."""
    t = regions.of(ctx)
    if t is None:
        return None
    ms = regions.ms_per_step(t, params["regions"])
    if params.get("share"):
        return 100.0 * ms / (t["busy_ns"] / t["steps"] / 1e6)
    return ms or None
