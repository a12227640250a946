#!/usr/bin/env python3
"""The program's own scopes and spans, read back from the traced run.

The program names its device work with ``jax.named_scope`` (the op
metadata's ``op_name`` path) and its host work with
``jax.profiler.TraceAnnotation`` spans named ``dppasgd.*``. Both land in
the ``.xplane.pb`` that a ``--trace 1`` run records, on the clock of the
device's ``XLA Ops``:

    scope / span          where                       read by
    dppasgd.local_grad    core/clipping.py            local_grad_share
    dppasgd.clip_noise    core/clipping.py            dp_path_share
    rwkv6.wkv_scan        models/rwkv.py              wkv_scan_share
    dppasgd.host_read     api/state.py                host_reads_per_round,
                                                      driver_idle_share
    dppasgd.batch         api/state.py                driver_idle_share
    dppasgd.ledger        api/state.py                driver_idle_share
    dppasgd.round         api/state.py                (groups the above)

``load`` parses one ``.xplane.pb`` (once per path) into a compact form:

    devices    {device: [[start_ns, duration_ns, hlo_op, kind, scope_paths]]}
    host       [[start_ns, duration_ns, name, args]] of the dppasgd.* spans
    window_ns  the harness's ``bench.window`` span

``hlo_op`` and ``kind`` are ``xtrace.classify``'s; ``scope_paths`` are the
op_name paths the op stands for (``op_paths``): its own op_name, or, for
an op XLA built around a loop's state, those of the ops it fuses or
copies; empty where the trace gives none. ``current`` is the newest trace
under ``.bench_out/trace``, the directory the harness writes and removes
only after the readers ran. A scope matches a path segment once transform
wrappers (``jvp(``, ``transpose(``) are stripped, so an op of the
backward pass counts under its forward scope. Containers (``while``,
``conditional``, ``call``) span their bodies and count under no scope. A
scope's time is the union of its ops' intervals clipped to the window,
so a share cannot pass 100%. Readers return ``None`` where no event
carries the scope or span.

Recording the small trace the tests read (one slice of two traced rounds
of a cell on the chip, around the round boundary)::

    python3 bench/scopes.py --record --workload <cell> --out bench/testdata/trace_scopes.json

The benchmark's own runs do not record.
"""
from __future__ import annotations

import argparse
import functools
import json
import re
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import catalog  # noqa: E402
import xtrace  # noqa: E402

TRACE_DIR = catalog.ROOT / ".bench_out" / "trace"
SPAN_PREFIX = "dppasgd."
HOST_READ = "dppasgd.host_read"
DRIVER_SPANS = ("dppasgd.batch", "dppasgd.ledger", HOST_READ)
_WRAPPER = re.compile(r"(?:jvp|transpose)\(")
_INSTR_NAME = re.compile(r"%([\w.\-]+) = ")
# the TPU runtime's host event that enqueues one program run
ENQUEUE = "DoEnqueueProgram"


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        value |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} not supported")
        yield key >> 3, value


def _ints(value) -> list[int]:
    """A repeated int64 field's entry: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def _text(value) -> str:
    return bytes(value).decode()


def hlo_paths(module) -> dict[str, tuple[str, ...]]:
    """Each instruction of one ``HloModuleProto`` -> the op_name paths it
    stands for (``xla/service/hlo.proto``: HloModuleProto.computations 3;
    HloComputationProto.instructions 2, .id 5; HloInstructionProto.name 1,
    .opcode 2, .metadata 7, .id 35, .operand_ids 36,
    .called_computation_ids 38; OpMetadata.op_name 2).

    An instruction whose op_name ends in a control-flow op (``while``,
    ``cond``, ``closed_call``) carries that op's name, not its own: XLA
    gave it the name when it built the instruction around the loop's
    state (the DP path's flat buffers, for one). Such an instruction
    stands for the op_names of the instructions it fuses, or else of its
    operands; a control-flow instruction stands for none."""
    insts, comps = {}, {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        cid, members = None, []
        for g, v in _fields(comp):
            if g == 5:
                cid = v
            elif g == 2:
                rec = {"name": "", "opcode": "", "op_name": "", "id": None,
                       "operands": [], "called": []}
                for h, w in _fields(v):
                    if h == 1:
                        rec["name"] = _text(w)
                    elif h == 2:
                        rec["opcode"] = _text(w)
                    elif h == 7:
                        rec["op_name"] = _text(dict(_fields(w)).get(2, b""))
                    elif h == 35:
                        rec["id"] = w
                    elif h == 36:
                        rec["operands"] += _ints(w)
                    elif h == 38:
                        rec["called"] += _ints(w)
                insts[rec["id"]] = rec
                members.append(rec["id"])
        comps[cid] = members
    memo: dict = {}

    def paths(iid) -> frozenset:
        if iid in memo:
            return memo[iid]
        memo[iid] = frozenset()            # a cycle stands for nothing
        rec = insts[iid]
        if rec["opcode"] in ("while", "conditional", "call", "parameter"):
            return memo[iid]
        if rec["op_name"] and rec["op_name"].rsplit("/", 1)[-1] not in (
                "while", "cond", "closed_call"):
            out = frozenset([rec["op_name"]])
        else:
            out = frozenset().union(*(paths(j) for c in rec["called"]
                                      for j in comps.get(c, ())))
            if not out:
                out = frozenset().union(*(paths(j) for j in rec["operands"]
                                          if j in insts))
        memo[iid] = out
        return out

    return {rec["name"]: tuple(sorted(paths(i))) for i, rec in insts.items()}


def op_paths(data: bytes) -> dict[str, dict[str, tuple[str, ...]]]:
    """Per TPU plane, each XLA op's event text -> the op_name paths it
    stands for.

    The TPU trace carries an op's op_name as the ``tf_op`` stat
    (``"<op_name>:<op type>"``) of the event's metadata, beside its
    ``program_id``; the optimized HLO of each program is in the
    ``/host:metadata`` plane (stat ``Hlo Proto``, an ``HloProto`` whose
    field 1 is the module). ``jax.profiler.ProfileData`` exposes neither,
    so this reads the XSpace proto (``tsl/profiler/protobuf/xplane.proto``:
    XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5;
    XEventMetadata.name 2, .stats 5; XStat.metadata_id 1, .uint64_value 3,
    .str_value 5, .bytes_value 6, .ref_value 7; XStatMetadata.id 1, .name
    2; map entries key 1, value 2). Where the program's HLO names the
    instruction, ``hlo_paths`` decides; else the ``tf_op``."""
    planes = []
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif f == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        decoded = []
        for event in events:
            text, stats = "", {}
            for f, v in _fields(event):
                if f == 2:
                    text = _text(v)
                elif f == 5:
                    stat = dict(_fields(v))
                    key = stat_names.get(stat.get(1), "")
                    if 7 in stat:
                        stats[key] = stat_names.get(stat[7], "")
                    elif 5 in stat:
                        stats[key] = _text(stat[5])
                    else:
                        stats[key] = stat.get(6, stat.get(3))
            decoded.append((text, stats))
        planes.append((name, decoded))

    programs = {}
    for name, decoded in planes:
        if name == "/host:metadata":
            for text, stats in decoded:
                m = re.search(r"\((\d+)\)$", text)
                if m and stats.get("Hlo Proto") is not None:
                    module = dict(_fields(stats["Hlo Proto"])).get(1, b"")
                    programs[int(m.group(1))] = hlo_paths(module)
    out = {}
    for name, decoded in planes:
        if not name.startswith("/device:TPU:"):
            continue
        paths = {}
        for text, stats in decoded:
            m = _INSTR_NAME.match(text)
            hlo = programs.get(stats.get("program_id"), {})
            if m and m.group(1) in hlo:
                paths[text] = hlo[m.group(1)]
            elif stats.get("tf_op"):
                paths[text] = (stats["tf_op"].rsplit(":", 1)[0],)
        out[name.rsplit("/", 1)[-1]] = paths
    return out


@functools.lru_cache(maxsize=1)
def load(path: str) -> dict:
    """The compact form of one ``.xplane.pb``, device events on the host's
    clock (``device_lead_ns``)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    names = op_paths(raw)
    data = ProfileData.from_serialized_xspace(raw)
    devices, host, window = {}, [], []
    launches, enqueues = {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.rsplit("/", 1)[-1]
            paths = names.get(dev, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[dev] = [
                        [e.start_ns, e.duration_ns, *xtrace.classify(e.name),
                         list(paths.get(e.name, ()))] for e in line.events]
                elif line.name == "XLA Modules":
                    launches[dev] = {dict(e.stats).get("run_id"): e.start_ns
                                     for e in line.events}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.start_ns, e.duration_ns, e.name,
                                     {k: v for k, v in e.stats}])
                    elif e.name == xtrace.WINDOW_SPAN:
                        window.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name == ENQUEUE:
                        st = dict(e.stats)
                        key = (f"device:TPU:{st.get('device_ordinal')}",
                               st.get("run_id"))
                        enqueues[key] = e.start_ns
    for dev, events in devices.items():
        lead = device_lead_ns(launches.get(dev, {}), {
            run: t for (d, run), t in enqueues.items() if d == dev})
        for e in events:
            e[0] += lead
    if window:
        window_ns = [min(a for a, _ in window), max(b for _, b in window)]
    else:
        window_ns = xtrace.window([], devices)
    return {"window_ns": window_ns, "devices": devices,
            "host": sorted(host, key=lambda e: e[0])}


def device_lead_ns(launches: dict, enqueues: dict) -> float:
    """How far the trace's device clock runs ahead of the host's: the
    largest lead of a program's start (``XLA Modules``) over the start of
    the host event that enqueued it (``DoEnqueueProgram``, paired by
    ``run_id``). A program cannot start before it is enqueued, so this is
    the least shift that puts every start after its enqueue; 0 where no
    pair is found."""
    leads = [enqueues[run] - start for run, start in launches.items()
             if run in enqueues]
    return max([0.0, *leads])


def newest(directory: Path) -> str | None:
    found = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return str(found[-1]) if found else None


def current() -> dict | None:
    """The traced run's compact trace, or ``None`` where there is none."""
    path = newest(TRACE_DIR)
    return load(path) if path else None


def save(trace: dict, path: str) -> None:
    """Write the compact form with each op_name path stored once."""
    table: dict[str, int] = {}
    devices = {dev: [e[:4] + [[table.setdefault(p, len(table))
                               for p in e[4]]] for e in events]
               for dev, events in trace["devices"].items()}
    with open(path, "w") as f:
        json.dump({"window_ns": trace["window_ns"], "paths": list(table),
                   "devices": devices, "host": trace["host"]}, f,
                  separators=(",", ":"))


def read(path: str) -> dict:
    with open(path) as f:
        saved = json.load(f)
    paths = saved.pop("paths")
    saved["devices"] = {
        dev: [e[:4] + [[paths[i] for i in e[4]]] for e in events]
        for dev, events in saved["devices"].items()}
    return saved


def carries(paths, scope: str) -> bool:
    """Does an op that stands for ``paths`` run under ``scope``?"""
    return any(scope in segments(p) for p in paths)


@functools.lru_cache(maxsize=1 << 16)
def segments(path: str) -> frozenset[str]:
    """The scope names of an op_name path, transform wrappers stripped:
    ``a/transpose(jvp(b))/c`` -> {a, b, c}."""
    out = set()
    for seg in path.split("/"):
        n = 0
        while m := _WRAPPER.match(seg):
            seg, n = seg[m.end():], n + 1
        if n and seg.endswith(")" * n):
            seg = seg[:-n]
        out.add(seg)
    return frozenset(out)


def _intersect(xs, ys) -> list[tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def scope_ns(trace: dict, device: str, scope: str) -> float | None:
    """Union of the device's op intervals under ``scope``, containers
    aside, inside the window; ``None`` where no op carries the scope."""
    lo, hi = trace["window_ns"]
    ops = [(s, s + d) for s, d, _, kind, paths in trace["devices"][device]
           if kind != "container" and carries(paths, scope)]
    if not ops:
        return None
    return xtrace._length(xtrace.union(xtrace._clip(ops, lo, hi)))


def idle(trace: dict, device: str) -> list[tuple[float, float]]:
    """The window's intervals in which no op (containers too, as for
    ``device_idle_share``) runs on the device."""
    lo, hi = trace["window_ns"]
    busy = xtrace.union(xtrace._clip(
        [(s, s + d) for s, d, *_ in trace["devices"][device]], lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def spans(trace: dict, names) -> list[list]:
    return [e for e in trace["host"] if e[2] in names]


def driver_idle_ns(trace: dict, device: str) -> float | None:
    """Device-idle time in the window that falls inside a batch, ledger or
    host-read span; ``None`` where the trace has none of those spans."""
    host = spans(trace, DRIVER_SPANS)
    if not host:
        return None
    lo, hi = trace["window_ns"]
    cover = xtrace.union(
        xtrace._clip([(s, s + d) for s, d, *_ in host], lo, hi))
    return xtrace._length(_intersect(idle(trace, device), cover))


def host_reads(trace: dict) -> int | None:
    """Host-read spans that start inside the window; ``None`` where the
    trace has no ``dppasgd.*`` span at all."""
    if not trace["host"]:
        return None
    lo, hi = trace["window_ns"]
    return sum(lo <= s < hi for s, *_ in spans(trace, (HOST_READ,)))


def device_share(ctx: dict, per_device) -> float | None:
    """``per_device(trace, device)`` ns over the window, in %, averaged
    over the cell's chips; ``None`` where there is no trace or no chip
    reads anything."""
    trace = current()
    if trace is None:
        return None
    values = [per_device(trace, d) for d in ctx["devices"]
              if d in trace["devices"]]
    if not values or all(v is None for v in values):
        return None
    lo, hi = trace["window_ns"]
    return 100.0 * sum(v or 0.0 for v in values) / len(values) / (hi - lo)


def scope_share(ctx: dict, scope: str) -> float | None:
    return device_share(ctx, lambda t, d: scope_ns(t, d, scope))


# ---------------------------------------------------------------------------
# recording the test slice
# ---------------------------------------------------------------------------

RECORD_ROUNDS = 2
MARGIN_NS = 1_000_000
SCAN_SCOPE = "rwkv6.wkv_scan"


def cut(trace: dict) -> dict:
    """A slice of the first device around the second round's start: from
    just before the first round's last ``dp_clip_noise`` call, through the
    round's end, its host reads and the next round's host spans, to just
    past the next round's first WKV scan op. The slice is the small
    trace's window."""
    dev = sorted(trace["devices"])[0]
    events = trace["devices"][dev]
    boundary = sorted(s for s, _, name, _ in trace["host"]
                      if name == "dppasgd.round")[1]
    kernel = max(s for s, _, name, kind, _ in events
                 if kind == "custom" and name == "dp_clip_noise"
                 and s < boundary)
    scan = min(s for s, _, _, kind, paths in events
               if kind != "container" and s > boundary
               and carries(paths, SCAN_SCOPE))
    lo, hi = kernel - MARGIN_NS, scan + MARGIN_NS
    keep = lambda s, d: s < hi and s + d > lo
    return {"window_ns": [lo, hi],
            "devices": {dev: [e for e in events if keep(e[0], e[1])]},
            "host": [e for e in trace["host"] if keep(e[0], e[1])]}


def record(workload: str, seed: int, out: str) -> dict:
    """Two traced rounds of ``workload`` on the chip, cut and saved."""
    sys.path.insert(0, str(catalog.ROOT / "src"))
    import jax

    import harness

    jax.config.update("jax_compilation_cache_dir", harness.cache_dir())
    cell = catalog.cell(workload)
    _, spec, state, traffic, make_weights, key = harness.build(cell, seed)
    state, _ = harness.checked_rounds(spec, state, traffic, make_weights, key)
    trace_dir = catalog.ROOT / ".bench_out" / "record-scopes"
    shutil.rmtree(trace_dir, ignore_errors=True)
    harness.window(spec, state, traffic, 60.0, RECORD_ROUNDS, trace_dir)
    small = cut(load(newest(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    save(small, out)
    return small


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--record", action="store_true", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    small = record(args.workload, args.seed, args.out)
    (dev,) = small["devices"]
    print(f"{args.out}: {len(small['devices'][dev])} device events, "
          f"{len(small['host'])} host spans")
    return 0


if __name__ == "__main__":
    sys.exit(main())
