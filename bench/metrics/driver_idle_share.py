"""driver_idle_share: the share of the traced window in which the device
is idle while the driver builds a batch, keeps the privacy ledger or
waits on a host read (inside a ``dppasgd.batch``, ``dppasgd.ledger`` or
``dppasgd.host_read`` span), averaged over the chips (``scopes.py``). At
most ``device_idle_share``: the rest of the idle time is dispatch and the
driver's own Python."""
import scopes


def read(ctx):
    return scopes.device_share(ctx, scopes.driver_idle_ns)
