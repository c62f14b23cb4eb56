"""Device milliseconds per step of the gradient codec's Pallas kernels: the
fused encode + error-feedback kernel (`_encode_call`), the unpack and
dequantize kernel and the Walsh-Hadamard kernel of the decode, matched by
their HLO instruction names in the trace and averaged over the devices
used. The glue around them (chunking pads, the worker mean) carries no
name the trace shows and is not counted."""
UNIT = "ms"
MOVES = "tokens_per_s"
PATTERNS = (r"_encode_call\b", r"unpack_dequant_pallas\b",
            r"quantize_pack_pallas\b", r"fwht_pallas\b")


def read(ctx):
    lo, hi = ctx.trace.window()
    per_device = []
    for dev in ctx.device_ids:
        ops = ctx.reduce.matching(ctx.trace.devices.get(dev, []), PATTERNS)
        if ops:
            per_device.append(sum(min(o.end, hi) - max(o.start, lo)
                                  for o in ops if o.end > lo and o.start < hi))
    if not per_device:
        return None
    return sum(per_device) / len(per_device) / ctx.trace.steps() * 1e-6
