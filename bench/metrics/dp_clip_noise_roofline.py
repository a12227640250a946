"""dp_clip_noise_roofline: the least time of the traced window's clip+noise
steps over the summed device time of the ``dp_clip_noise`` kernel's calls.

The least time counts 8 bytes per gradient element at the HBM peak (one
f32 read of the gradient, one f32 write of the result; the noise as drawn
on the chip), whatever implements the step: today's two passes with a
noise operand move 16, so they read 50% at most."""
import xtrace

KERNEL = "dp_clip_noise"


def read(ctx):
    times = [xtrace.kernel_ns(ctx["trace"], d, KERNEL)
             for d in ctx["devices"]]
    ns = sum(t for t, _ in times)
    if not ns:
        return None
    least = ctx["client_steps"] * ctx["dp_bytes"] / ctx["peaks"][
        "hbm_bytes_per_s"]
    return 100.0 * least / (ns / 1e9)
