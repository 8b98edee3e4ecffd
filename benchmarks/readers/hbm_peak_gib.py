def read(ctx, params):
    """The fullest chip's peak, in GiB; nothing off the chip."""
    peak = ctx.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak and ctx["peaks"] is not None else None
