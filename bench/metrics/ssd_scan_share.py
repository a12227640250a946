"""ssd_scan_share: the device time of the chunked SSD (intra-chunk
quadratic form, chunk states, inter-chunk scan, output), forward and
backward, every op under the ``mamba2.ssd_scan`` scope, as the union of
those ops' intervals over the traced window, averaged over the chips
(``scopes.py``). Part of ``mamba2_mixer_share``."""
import scopes


def read(ctx):
    return scopes.scope_share(ctx, "mamba2.ssd_scan")
