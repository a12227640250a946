"""host_reads_per_round: the driver's blocking device->host reads (its
``dppasgd.host_read`` spans: each metric value ``materialize_record``
converts, and a participation mask) that start in the traced window, over
the rounds traced (``scopes.py``)."""
import scopes


def read(ctx):
    trace = scopes.current()
    if trace is None or not ctx["rounds"]:
        return None
    reads = scopes.host_reads(trace)
    return None if reads is None else reads / ctx["rounds"]
