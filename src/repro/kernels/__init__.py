"""Pallas compute kernels behind a backend dispatch layer.

The kernels are the fused DP clip+noise update over a flat vector (paper
Eq. 7a / Eq. 23), the QSGD quantize round trip, the resident cohort
gather/scatter, and the model-side flash attention, RWKV6 WKV scan and
Mamba2 SSD chunk scan. The training step's own clip+noise is the leaf-wise
jnp ``ops.dp_clip_noise_tree``, which XLA fuses per leaf.

Kernel backends
---------------
Every kernel is registered in :mod:`repro.kernels.dispatch` with its
pure-jnp oracle from :mod:`repro.kernels.ref` (the parity reference and
the off-TPU default), and is selected by name + backend:

    from repro.kernels import get_kernel
    y, norm = get_kernel("dp_clip_noise")(g, noise, clip_norm, sigma)

========== ==============================================================
backend    meaning
========== ==============================================================
"pallas"   Mosaic-compiled Pallas (TPU only)
"interpret" ``pallas_call(interpret=True)`` — kernel body as jax ops (CPU)
"ref"      the pure-jnp oracle; always available
"auto"     ``KERNEL_BACKEND`` env var if set, else "pallas" on a TPU (or
           an error carrying the probe's exception) and "ref" elsewhere
========== ==============================================================

On a TPU the capability probe runs each kernel once on small tile-legal
shapes against its oracle; a kernel that fails it raises, so a chip run
never quietly runs interpret mode or the oracle. ``rwkv6_scan`` and
``mamba2_ssd`` are refused by the TPU compiler today (their block shapes
break the (8, 128) tiling), so ``auto`` raises for them on a TPU; the
model code does not call them.
The training path selects its backends declaratively through
``FederationSpec(kernel_backend=...)``; :mod:`repro.kernels.ops` carries
the plain-function wrappers. ``register_kernel`` adds new kernels without
touching any call site.
"""
from repro.kernels.dispatch import (
    KERNEL_BACKENDS,
    available_backends,
    backend_works,
    get_kernel,
    kernel_names,
    register_kernel,
    resolve_backend,
)

__all__ = [
    "KERNEL_BACKENDS", "available_backends", "backend_works", "get_kernel",
    "kernel_names", "register_kernel", "resolve_backend",
]
