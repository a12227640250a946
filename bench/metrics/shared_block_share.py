"""shared_block_share: the device time of Zamba2's shared transformer
blocks (the concatenation with the embeddings, both blocks' attention and
MLP, the per-invocation adapters and ``linear``), forward and backward,
every op under the ``zamba2.shared_block`` scope, as the union of those
ops' intervals over the traced window, averaged over the chips
(``scopes.py``). Part of ``local_grad_share``."""
import scopes


def read(ctx):
    return scopes.scope_share(ctx, "zamba2.shared_block")
