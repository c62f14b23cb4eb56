"""The codec's share of its roofline: the least time the chip could take
for the codec's minimum traffic and work (bench/codec_bytes.py), the larger
of bytes / peak bytes/s and ops / peak FLOP/s, over codec_ms. At 4 bits the
byte bound is the larger by three orders of magnitude."""
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    codec_ms = ctx.metric("codec_ms")
    if not codec_ms:
        return None
    need = ctx.codec_minimum
    t_min = max(need["bytes"] / ctx.peak["bytes_per_s"],
                need["ops"] / ctx.peak["flops_per_s"])
    return 100.0 * t_min / (codec_ms * 1e-3)
