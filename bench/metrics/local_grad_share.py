"""local_grad_share: the device time of the local step's forward and
backward (Eq. 7a's gradient), every op under the ``dppasgd.local_grad``
scope, as the union of those ops' intervals over the traced window,
averaged over the chips (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.scope_share(ctx, "dppasgd.local_grad")
