"""Share of the traced window in which no operation runs on the first
device used: 1 - busy union / window."""
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    ops = ctx.trace.devices.get(ctx.device_ids[0])
    if not ops:
        return None
    lo, hi = ctx.trace.window()
    return 100.0 * ctx.reduce.idle_share(ops, lo, hi)
