def read(ctx, params):
    """Summed host time of the named spans per step of the window, in ms."""
    found = [ctx["spans"][s] for s in params["spans"] if ctx["spans"].get(s)]
    if not found or not ctx["steps"]:
        return None
    return sum(sum(d) for d in found) / ctx["steps"] * 1e3
