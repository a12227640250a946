"""mamba2_mixer_share: the device time of the Mamba2 mixers, projections,
conv, SSD scan and gated norm included, forward and (through the
transpose) backward, every op under the ``mamba2.mixer`` scope, as the
union of those ops' intervals over the traced window, averaged over the
chips (``scopes.py``). Part of ``local_grad_share``."""
import scopes


def read(ctx):
    return scopes.scope_share(ctx, "mamba2.mixer")
