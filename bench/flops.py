"""Work counted from shapes, the chip's peaks, and the context the
per-layer metric readers are handed.

The architecture's own counts (``param_count``, ``flops_per_token``) live
in its reference module beside the plain reference; ``peaks.json`` holds
the published peaks of each ``device_kind``.
"""
from __future__ import annotations

from pathlib import Path

import catalog

# the DP clip+noise step's least HBM traffic per gradient element: one f32
# read of the gradient and one f32 write of the result; the noise counts
# as drawn on the chip (0 bytes), whatever implements the step
DP_BYTES_PER_ELEMENT = 8


def peaks(kind: str, path: Path | None = None) -> dict:
    table = catalog.load_json(path or catalog.BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def params(config: dict) -> int:
    return catalog.reference_module(config).param_count(config)


def round_flops(config: dict, mix: dict) -> float:
    """Model FLOPs of one round: every client-step's tokens."""
    per_token = catalog.reference_module(config).flops_per_token(
        config, int(mix["seq"]))
    tokens = int(mix["clients"]) * int(mix["tau"]) * int(mix["batch"]) * int(
        mix["seq"])
    return per_token * tokens


def dp_bytes(config: dict) -> float:
    """Least HBM bytes of one client-step's clip+noise."""
    return float(DP_BYTES_PER_ELEMENT * params(config))


def context(cell, traffic, rounds: int, devices) -> dict:
    """What a per-layer metric reader gets (the trace is added by the
    harness once it is read)."""
    return {
        "cell": cell.name, "config": cell.config, "mix": cell.traffic,
        "chips": cell.chips, "rounds": rounds,
        "client_steps": rounds * traffic.clients * traffic.tau,
        "round_flops": round_flops(cell.config, cell.traffic),
        "dp_bytes": dp_bytes(cell.config),
        "peaks": peaks(devices[0].device_kind),
    }
