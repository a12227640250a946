"""wkv_scan_share: the device time of RWKV-6's WKV recurrence, forward and
(through the transpose) backward, every op under the ``rwkv6.wkv_scan``
scope, as the union of those ops' intervals over the traced window,
averaged over the chips (``scopes.py``). Part of ``local_grad_share``."""
import scopes


def read(ctx):
    return scopes.scope_share(ctx, "rwkv6.wkv_scan")
