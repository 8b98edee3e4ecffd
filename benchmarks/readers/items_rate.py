def read(ctx, params):
    return ctx["items_per_s_per_chip"]
