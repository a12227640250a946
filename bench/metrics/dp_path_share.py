"""dp_path_share: the device time of the whole DP clip+noise path, every
op under the ``dppasgd.clip_noise`` scope (``dp_clip_noise_tree``'s
flatten and concatenation, the noise draw, the kernel, the slice and
convert back), as the union of those ops' intervals over the traced
window, averaged over the chips (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.scope_share(ctx, "dppasgd.clip_noise")
