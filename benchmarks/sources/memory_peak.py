"""Peak bytes in use on the fullest chip, as the device reports it."""


def read(ctx, args):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes * args.get("scale", 1)
