"""Kernel backend dispatch: registry + capability-probed auto-selection.

Mirrors the round-engine registry of ``repro.api.engines`` one layer down:
every compute kernel is registered under a name with

  * its Pallas implementation (accepting an ``interpret`` kwarg), and
  * its pure-jnp oracle from :mod:`repro.kernels.ref` — the reference the
    parity tests compare against, and the off-TPU default.

Backends
--------
``"pallas"``     Mosaic-compiled Pallas (requires a TPU backend).
``"interpret"``  ``pallas_call(interpret=True)`` — same kernel body executed
                 as jax ops; the CPU rehearsal of the TPU kernel.
``"ref"``        the pure-jnp oracle; always available.
``"auto"``       the ``KERNEL_BACKEND`` environment variable if set; else
                 ``"pallas"`` on a TPU and ``"ref"`` everywhere else.

There is no fallback on the chip: on a TPU, ``auto`` runs the kernel's
capability probe (a small tile-legal call checked against the oracle) once
and resolves to ``"pallas"``, or raises with the probe's own exception
attached. It never quietly demotes to ``"interpret"`` or ``"ref"`` there.

Usage::

    from repro.kernels.dispatch import get_kernel
    y, norm = get_kernel("dp_clip_noise")(g, noise, clip_norm, sigma)
    fa = get_kernel("flash_attention", backend="interpret")

``register_kernel`` adds new kernels without touching call sites; the
engine hot path selects purely via ``FederationSpec.kernel_backend``.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

KERNEL_BACKENDS = ("pallas", "interpret", "ref", "auto")
KERNEL_BACKEND_ENV = "KERNEL_BACKEND"
# comma-separated backends to report as unavailable (capability simulation:
# CI's oracle-only leg sets "pallas,interpret" to rehearse a broken pallas)
KERNEL_DISABLE_ENV = "KERNEL_DISPATCH_DISABLE"


@dataclass(frozen=True)
class KernelEntry:
    """One registered kernel: Pallas impl + oracle + capability probe."""
    name: str
    pallas_fn: Callable | None      # accepts interpret=... keyword
    ref_fn: Callable                # pure-jnp oracle (ignores tuning kwargs)
    probe: Callable[[Callable], bool] | None  # smoke test given a bound impl


_REGISTRY: dict[str, KernelEntry] = {}


def register_kernel(name: str, *, ref: Callable, pallas: Callable | None = None,
                    probe: Callable[[Callable], bool] | None = None) -> KernelEntry:
    """Register ``name`` with its oracle and (optionally) its Pallas impl.

    ``ref`` must share the Pallas impl's positional signature and swallow its
    tuning keywords (block sizes etc.) so callers can pass them uniformly.
    """
    entry = KernelEntry(name=name, pallas_fn=pallas, ref_fn=ref, probe=probe)
    _REGISTRY[name] = entry
    return entry


def kernel_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _entry(name: str) -> KernelEntry:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered: "
                       f"{kernel_names()}") from None


def _bind(entry: KernelEntry, backend: str) -> Callable:
    if backend == "ref":
        return entry.ref_fn
    if entry.pallas_fn is None:
        raise ValueError(f"kernel {entry.name!r} has no pallas implementation")
    return functools.partial(entry.pallas_fn,
                             interpret=(backend == "interpret"))


def _disabled_backends() -> frozenset[str]:
    raw = os.environ.get(KERNEL_DISABLE_ENV, "")
    return frozenset(b.strip() for b in raw.split(",") if b.strip())


@functools.lru_cache(maxsize=None)
def probe_error(name: str, backend: str) -> Exception | None:
    """Run ``name``'s capability probe on ``backend`` once per process.

    Returns None when the probe passes, else the exception it raised (an
    oracle mismatch is reported as an ``AssertionError``). The probe runs
    real arrays, so it must not run under a jit trace: engine builders
    resolve their backends eagerly, at build time
    (``clipping.make_dp_grad_fn``, ``aggregation.make_compressor``,
    ``fl.make_resident_chunked_round``).
    """
    entry = _entry(name)
    if entry.probe is None:
        return LookupError(f"kernel {name!r} registers no capability probe")
    try:
        ok = entry.probe(_bind(entry, backend))
    except Exception as exc:          # reported to the caller, not dropped
        return exc
    if not ok:
        return AssertionError(f"{name!r} on {backend!r} disagrees with its "
                              f"oracle on the probe inputs")
    return None


def backend_works(name: str, backend: str) -> bool:
    """Capability probe (cached): does ``backend`` run ``name`` here?

    "ref" is always True. Backends named in ``KERNEL_DISPATCH_DISABLE``
    read as unavailable without probing (oracle-only rehearsal). "pallas"
    (Mosaic-compiled) additionally requires a TPU default backend before
    the probe is even attempted. :func:`probe_error` keeps the reason of a
    failed probe.
    """
    if backend == "ref":
        return True
    if backend in _disabled_backends():
        return False
    entry = _entry(name)
    if entry.pallas_fn is None:
        return False
    if backend == "pallas" and jax.default_backend() != "tpu":
        return False
    return probe_error(name, backend) is None


# probe-cache reset for tests/tooling
backend_works.cache_clear = probe_error.cache_clear


def available_backends(name: str) -> tuple[str, ...]:
    """Concrete backends (probe-verified) for ``name``, best first."""
    return tuple(b for b in ("pallas", "interpret", "ref")
                 if backend_works(name, b))


def resolve_backend(name: str, backend: str = "auto") -> str:
    """Map ``backend="auto"`` to a concrete backend for this process.

    An explicit non-auto argument wins untouched (callers get the real
    error if they force a broken backend); else the ``KERNEL_BACKEND`` env
    var if set; else ``"pallas"`` on a TPU and ``"ref"`` elsewhere. Off the
    TPU the jnp oracle is the fast path: ``pallas_call(interpret=True)``
    executes the kernel body block by block as jax ops, ~100x slower on CPU
    (measured in benchmarks/throughput.py), so interpret mode stays an
    explicit choice (``backend="interpret"`` / ``KERNEL_BACKEND=interpret``)
    for the parity suites.

    On a TPU the Pallas kernel is the only answer: if its probe fails, this
    raises ``RuntimeError`` chained to the probe's exception.
    """
    if backend not in KERNEL_BACKENDS:
        raise ValueError(f"backend must be one of {KERNEL_BACKENDS}, "
                         f"got {backend!r}")
    if backend != "auto":
        return backend
    env = os.environ.get(KERNEL_BACKEND_ENV, "").strip()
    if env and env != "auto":
        if env not in KERNEL_BACKENDS:
            raise ValueError(f"${KERNEL_BACKEND_ENV}={env!r} is not one of "
                             f"{KERNEL_BACKENDS}")
        return env
    if jax.default_backend() != "tpu":
        return "ref"
    if backend_works(name, "pallas"):
        return "pallas"
    if "pallas" in _disabled_backends():
        raise RuntimeError(f"kernel {name!r}: pallas is disabled by "
                           f"${KERNEL_DISABLE_ENV} on a TPU")
    entry = _entry(name)
    if entry.pallas_fn is None:
        raise RuntimeError(f"kernel {name!r} has no pallas implementation "
                           f"to run on a TPU")
    raise RuntimeError(f"kernel {name!r} failed its pallas probe on this "
                       f"TPU") from probe_error(name, "pallas")


def get_kernel(name: str, backend: str = "auto") -> Callable:
    """The ``name`` kernel bound to a concrete backend.

    The returned callable has the kernel's public signature (oracle-compatible
    positional args; tuning kwargs accepted by every backend).
    """
    return _bind(_entry(name), resolve_backend(name, backend))


# ---------------------------------------------------------------------------
# built-in kernels: oracle adapters + smoke probes
# ---------------------------------------------------------------------------
# Adapters give every backend one signature: the oracle swallows the Pallas
# tuning kwargs. Probes run small shapes that the TPU tiling accepts through
# the bound impl and compare against the oracle — cheap enough to pay once
# per process.

def _close(a, b, tol=1e-4) -> bool:
    return all(bool(jnp.allclose(jnp.asarray(x, jnp.float32),
                                 jnp.asarray(y, jnp.float32),
                                 rtol=tol, atol=tol))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _dp_clip_noise_oracle(g, noise, clip_norm, sigma, **_tuning):
    return _ref.dp_clip_noise_ref(g, noise, clip_norm, sigma)


def _dp_clip_noise_probe(impl) -> bool:
    g = jnp.linspace(-2.0, 3.0, 5000, dtype=jnp.float32)
    noise = jnp.ones((5000,), jnp.float32)
    got = impl(g, noise, 1.0, 0.25)
    return _close(got, _ref.dp_clip_noise_ref(g, noise, 1.0, 0.25))


def _quantize_decompress_oracle(x, u, bits, **_tuning):
    return _ref.quantize_decompress_ref(x, u, bits)


def _quantize_decompress_probe(impl) -> bool:
    x = jnp.linspace(-3.0, 2.0, 5000, dtype=jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(3), (5000,), jnp.float32)
    got = impl(x, u, 4)
    return _close(got, _ref.quantize_decompress_ref(x, u, 4))


def _flash_attention_oracle(q, k, v, *, causal=True, window=0, **_tuning):
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def _flash_attention_probe(impl) -> bool:
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 256, 64), jnp.float32)
               for kk in ks)
    got = impl(q, k, v, block_q=128, block_k=128)
    return _close(got, _ref.flash_attention_ref(q, k, v))


def _rwkv6_scan_oracle(r, k, v, w, u, s0=None, **_tuning):
    return _ref.rwkv6_scan_ref(r, k, v, w, u, s0)


def _rwkv6_scan_probe(impl) -> bool:
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r, k, v = (jax.random.normal(kk, (1, 1, 3, 4), jnp.float32)
               for kk in ks[:3])
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (1, 1, 3, 4)))
    u = jax.random.normal(ks[4], (1, 4), jnp.float32)
    got = impl(r, k, v, w, u)
    return _close(got, _ref.rwkv6_scan_ref(r, k, v, w, u))


def _mamba2_ssd_oracle(x, dt, a, b_in, c_in, **_tuning):
    return _ref.mamba2_ssd_ref(x, dt, a, b_in, c_in)


def _mamba2_ssd_probe(impl) -> bool:
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (1, 4, 1, 2), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 4, 1)))
    a = -jnp.exp(jax.random.normal(ks[2], (1,)) * 0.3)
    b_in = jax.random.normal(ks[3], (1, 4, 2), jnp.float32)
    c_in = jax.random.normal(ks[4], (1, 4, 2), jnp.float32)
    got = impl(x, dt, a, b_in, c_in, chunk=4)
    return _close(got, _ref.mamba2_ssd_ref(x, dt, a, b_in, c_in), tol=1e-3)


def _cohort_gather_scatter_oracle(cache, slots, rows=None, **_tuning):
    return _ref.cohort_gather_scatter_ref(cache, slots, rows)


def _cohort_gather_scatter_probe(impl) -> bool:
    cache = jnp.arange(9 * 1024, dtype=jnp.float32).reshape(9, 1024)
    slots = jnp.asarray([7, 0, 4], jnp.int32)
    rows = -jnp.arange(3 * 1024, dtype=jnp.float32).reshape(3, 1024)
    got_g = impl(cache, slots)
    got_s = impl(cache, slots, rows)
    return (_close(got_g, _ref.cohort_gather_scatter_ref(cache, slots))
            and _close(got_s,
                       _ref.cohort_gather_scatter_ref(cache, slots, rows)))


def _register_builtins() -> None:
    from repro.kernels.cohort_gather import cohort_gather_scatter
    from repro.kernels.dp_clip_noise import dp_clip_noise
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.mamba2_ssd import mamba2_ssd
    from repro.kernels.quantize_decompress import quantize_decompress
    from repro.kernels.rwkv6_scan import rwkv6_scan

    register_kernel("dp_clip_noise", pallas=dp_clip_noise,
                    ref=_dp_clip_noise_oracle, probe=_dp_clip_noise_probe)
    register_kernel("quantize_decompress", pallas=quantize_decompress,
                    ref=_quantize_decompress_oracle,
                    probe=_quantize_decompress_probe)
    register_kernel("flash_attention", pallas=flash_attention,
                    ref=_flash_attention_oracle, probe=_flash_attention_probe)
    register_kernel("rwkv6_scan", pallas=rwkv6_scan,
                    ref=_rwkv6_scan_oracle, probe=_rwkv6_scan_probe)
    register_kernel("mamba2_ssd", pallas=mamba2_ssd,
                    ref=_mamba2_ssd_oracle, probe=_mamba2_ssd_probe)
    register_kernel("cohort_gather_scatter", pallas=cohort_gather_scatter,
                    ref=_cohort_gather_scatter_oracle,
                    probe=_cohort_gather_scatter_probe)


_register_builtins()
