"""From the profiler's trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps a
compact form: per device, the events of its ``XLA Ops`` line as
``[start_ns, duration_ns, name, kind]``, and the host threads' events as
``[start_ns, duration_ns, name, thread]``; host and device share one
clock. ``name`` is the HLO instruction's name without its ``%`` and
numeric suffix (``%dp_clip_noise.25 = ...`` -> ``dp_clip_noise``);
``kind`` is one of

    "container"   while / conditional / call: spans the ops of its body
    "collective"  all-reduce, all-gather, reduce-scatter, ... (and -start)
    "custom"      a custom call (a Pallas kernel is a ``tpu_custom_call``)
    "op"          anything else

The window is the host span ``bench.window`` that the harness opens
around the measured calls. The functions below work on the compact form,
so the small trace checked in under ``bench/testdata`` tests them.
"""
from __future__ import annotations

import json
import re

WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")


def classify(text: str) -> tuple[str, str]:
    """(name, kind) of one ``XLA Ops`` event's text."""
    m = _INSTR.match(text)
    name = m.group(1) if m else text.split(" ", 1)[0]
    if name in CONTAINERS:
        kind = "container"
    elif name.startswith(COLLECTIVES) or "all-reduce" in name:
        kind = "collective"
    elif "custom-call(" in text:
        kind = "custom"
    else:
        kind = "op"
    return name, kind


def load(path: str) -> dict:
    """The compact form of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name.rsplit("/", 1)[-1]] = [
                        [e.start_ns, e.duration_ns, *classify(e.name)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.start_ns, e.duration_ns, e.name, line.name]
                            for e in line.events)
    return {"window_ns": window(host, devices), "devices": devices,
            "host": host}


def window(host: list, devices: dict) -> list:
    spans = [(s, s + d) for s, d, name, _ in host if name == WINDOW_SPAN]
    if spans:
        return [min(a for a, _ in spans), max(b for _, b in spans)]
    ev = [(s, s + d) for evs in devices.values() for s, d, *_ in evs]
    return [min(a for a, _ in ev), max(b for _, b in ev)]


def save(trace: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)


def read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def busy_ns(trace: dict, device: str) -> float:
    """Union of the device's op intervals inside the window."""
    lo, hi = trace["window_ns"]
    ev = trace["devices"][device]
    return _length(union(_clip([(s, s + d) for s, d, *_ in ev], lo, hi)))


def kernel_ns(trace: dict, device: str, name: str) -> tuple[float, int]:
    """Summed device time and count of the custom calls named ``name``
    that start inside the window."""
    lo, hi = trace["window_ns"]
    hits = [d for s, d, n, k in trace["devices"][device]
            if k == "custom" and n == name and lo <= s < hi]
    return float(sum(hits)), len(hits)


def top_ops(trace: dict, n: int = 10) -> list[list]:
    """The ``n`` op names with the most device time (containers aside),
    in seconds, averaged over the devices."""
    lo, hi = trace["window_ns"]
    total: dict[str, float] = {}
    for ev in trace["devices"].values():
        for s, d, name, kind in ev:
            if kind != "container" and lo <= s < hi:
                total[name] = total.get(name, 0.0) + d
    k = max(len(trace["devices"]), 1)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in top]


def idle_gaps(trace: dict, device: str, n: int = 10) -> list[list]:
    """The ``n`` longest gaps in the device's busy time inside the window,
    each named by the shortest host span that covers its middle (what the
    host was doing), in seconds."""
    lo, hi = trace["window_ns"]
    busy = union(_clip([(s, s + d) for s, d, *_ in trace["devices"][device]],
                       lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [(d, name) for s, d, name, _ in trace["host"]
                 if s <= mid < s + d and name != WINDOW_SPAN]
        out.append([min(cover)[1] if cover else "no host span",
                    (b - a) / 1e9])
    return out
