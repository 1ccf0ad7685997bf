"""Share of its roofline that the static build's encode + key-pack kernel
(``kernels/build_fused.py`` through ``ops.encode_pack``) reaches: the
least time of its work (``roofline.encode_pack_floor``) per build, times
the builds, over the kernel's device time in the trace."""

from bench.roofline import encode_pack_floor

ENCODE_PACK = r"^encode_pack(\.\d+)?$"


def read(ctx):
    builds = ctx.counters.get("builds")
    if ctx.trace is None or not builds:
        return None
    seconds = ctx.trace.op_seconds(ENCODE_PACK)
    if seconds <= 0:
        return None
    c = ctx.counters
    floor = builds * encode_pack_floor(c["n"], c["K"], c["L"], ctx.peak)
    return 100.0 * floor / seconds
