"""dp_clip_noise_share: the summed device time of the ``dp_clip_noise``
kernel's calls (its norm pass and its scale+noise pass, both custom calls
of that name) over the traced window, averaged over the chips."""
import xtrace

KERNEL = "dp_clip_noise"


def read(ctx):
    times = [xtrace.kernel_ns(ctx["trace"], d, KERNEL)
             for d in ctx["devices"]]
    if not any(n for _, n in times):
        return None
    ns = sum(t for t, _ in times) / len(times)
    return 100.0 * ns / 1e9 / ctx["window_s"]
