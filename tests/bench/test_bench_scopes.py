"""The reduction from the program's scopes and spans to the per-layer
metrics (``bench/scopes.py``), on hand-made events and on the slice
recorded on a v5e (``bench/testdata/trace_scopes.json``: the end of one
IoT round and the start of the next, with the driver's host spans)."""
import jax
import pytest

import catalog
import scopes
import xtrace

RECORDED = catalog.BENCH / "testdata" / "trace_scopes.json"
NEW_METRICS = ("dp_path_share", "local_grad_share", "wkv_scan_share",
               "host_reads_per_round", "driver_idle_share")

LG = "jit(round_step)/while/body/closed_call/dppasgd.local_grad"
CN = "jit(round_step)/while/body/closed_call/dppasgd.clip_noise"


def hand_trace():
    """Window 100..1100 on one device: a while container over it all; two
    overlapping local-step ops, one of them a backward op of the scan that
    starts before the window; the DP path's kernel beside a copy of its
    flatten; an unscoped update op; host spans around an idle gap."""
    ev = [[0, 1200, "while", "container", [CN]],
          [50, 150, "fusion", "op",
           [LG + "/transpose(jvp())/while/body/rwkv6.wkv_scan/mul"]],
          [150, 150, "fusion", "op", [LG + "/jvp()/dot_general"]],
          [400, 100, "copy", "op", [CN + "/concatenate"]],
          [450, 100, "dp_clip_noise", "custom", [CN + "/pallas_call"]],
          [600, 100, "add", "op", ["jit(round_step)/add"]],
          [900, 100, "fusion", "op", [LG + "/jvp()/dot_general"]]]
    host = [[650, 200, "dppasgd.host_read", {}],
            [860, 20, "dppasgd.ledger", {}],
            [880, 30, "dppasgd.batch", {}],
            [1000, 50, "dppasgd.host_read", {}]]
    return {"window_ns": [100, 1100], "devices": {"TPU:0": ev},
            "host": host}


@pytest.fixture(scope="module")
def recorded():
    return scopes.read(str(RECORDED))


def test_segments_strip_transform_wrappers():
    path = "jit(f)/a/transpose(jvp(dppasgd.local_grad))/jvp()/b"
    assert {"a", "dppasgd.local_grad", "b"} <= scopes.segments(path)
    assert "rwkv6.wkv_scan" in scopes.segments(
        "x/transpose(jvp())/while/body/rwkv6.wkv_scan/mul")
    assert "dppasgd.clip" not in scopes.segments(CN + "/mul")


def test_scope_time_is_a_union_clipped_to_the_window_without_containers():
    t = hand_trace()
    # local step: [100, 300) from two overlapping ops (50-200 clipped to
    # 100-200, 150-300) and [900, 1000): a union, not a sum
    assert scopes.scope_ns(t, "TPU:0", "dppasgd.local_grad") == 300
    # the scan's backward op counts under the scan
    assert scopes.scope_ns(t, "TPU:0", "rwkv6.wkv_scan") == 100
    # the DP path: copy and kernel, 400-550; the while container that
    # carries the same scope is left out
    assert scopes.scope_ns(t, "TPU:0", "dppasgd.clip_noise") == 150
    assert scopes.scope_ns(t, "TPU:0", "no.such_scope") is None


def test_driver_idle_is_idle_time_inside_the_driver_spans():
    t = hand_trace()
    t["devices"]["TPU:0"] = t["devices"]["TPU:0"][1:]     # no container
    # idle: 300-400, 550-600, 700-900, 1000-1100; driver spans cover
    # 650-850, 860-910 and 1000-1050: 700-850 (read) + 860-900 (ledger,
    # batch) + 1000-1050 (read)
    assert scopes.idle(t, "TPU:0") == [(300, 400), (550, 600), (700, 900),
                                       (1000, 1100)]
    assert scopes.driver_idle_ns(t, "TPU:0") == 150 + 40 + 50
    assert scopes.host_reads(t) == 2


def test_no_scopes_or_spans_read_none(monkeypatch):
    t = hand_trace()
    t["devices"]["TPU:0"] = [e[:4] + [[]] for e in t["devices"]["TPU:0"]]
    t["host"] = []
    assert scopes.scope_ns(t, "TPU:0", "dppasgd.clip_noise") is None
    assert scopes.driver_idle_ns(t, "TPU:0") is None
    assert scopes.host_reads(t) is None
    monkeypatch.setattr(scopes, "current", lambda: t)
    ctx = {"devices": ["TPU:0"], "rounds": 1}
    for name in NEW_METRICS:
        assert catalog.metric_reader(name)(ctx) is None, name
    # no trace at all: the parent's untraced side, or a missing file
    monkeypatch.setattr(scopes, "current", lambda: None)
    for name in NEW_METRICS:
        assert catalog.metric_reader(name)(ctx) is None, name


def test_no_trace_directory_is_no_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path / "absent")
    assert scopes.current() is None


def test_load_keeps_the_program_spans_and_the_window(tmp_path):
    """A host-only trace (the CPU has no ``/device:TPU`` plane): the
    ``dppasgd.*`` spans with their arguments, inside ``bench.window``."""
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("dppasgd.round", round=7):
                with jax.profiler.TraceAnnotation("dppasgd.host_read"):
                    jax.block_until_ready(jax.numpy.ones(3) + 1)
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    t = scopes.load(scopes.newest(tmp_path))
    names = [e[2] for e in t["host"]]
    assert names == ["dppasgd.round", "dppasgd.host_read"]
    (rnd,) = [e for e in t["host"] if e[2] == "dppasgd.round"]
    assert rnd[3]["round"] == 7
    lo, hi = t["window_ns"]
    assert all(lo <= s and s + d <= hi for s, d, *_ in t["host"])
    assert scopes.host_reads(t) == 1


def test_recorded_slice_holds_a_round_boundary(recorded):
    (dev,) = recorded["devices"]
    names = {e[2] for e in recorded["host"]}
    assert {"dppasgd.round", "dppasgd.batch", "dppasgd.ledger",
            "dppasgd.host_read"} <= names
    for scope in ("dppasgd.local_grad", "dppasgd.clip_noise",
                  "rwkv6.wkv_scan"):
        assert scopes.scope_ns(recorded, dev, scope) > 0, scope
    assert RECORDED.stat().st_size < 200_000


def test_dp_path_holds_the_kernel(recorded):
    (dev,) = recorded["devices"]
    # the kernel's time as dp_clip_noise_share reads it (xtrace.kernel_ns)
    kernel, calls = xtrace.kernel_ns(
        {"window_ns": recorded["window_ns"],
         "devices": {dev: [e[:4] for e in recorded["devices"][dev]]}},
        dev, "dp_clip_noise")
    assert calls >= 1
    assert scopes.scope_ns(recorded, dev, "dppasgd.clip_noise") >= kernel
    # every kernel call sits under the DP path's scope
    assert all(scopes.carries(paths, "dppasgd.clip_noise")
               for _, _, n, k, paths in recorded["devices"][dev]
               if k == "custom" and n == "dp_clip_noise")


def test_scope_shares_fit_inside_the_busy_time(recorded, monkeypatch):
    (dev,) = recorded["devices"]
    monkeypatch.setattr(scopes, "current", lambda: recorded)
    ctx = {"devices": [dev], "rounds": 1}
    read = {m: catalog.metric_reader(m)(ctx) for m in NEW_METRICS}
    lo, hi = recorded["window_ns"]
    busy = 100.0 * xtrace.busy_ns(recorded, dev) / (hi - lo)
    assert read["wkv_scan_share"] <= read["local_grad_share"]
    assert read["local_grad_share"] + read["dp_path_share"] <= busy + 1e-9
    assert 0 < read["driver_idle_share"] <= 100.0 - busy + 1e-9


def test_host_spans_and_device_ops_share_one_clock(recorded):
    """The driver's host reads end after the round's last device op, and
    the next round's device work starts after its batch was built."""
    (dev,) = recorded["devices"]
    ops = [(s, s + d) for s, d, _, k, _ in recorded["devices"][dev]
           if k != "container"]
    starts = sorted(s for s, _, n, _ in recorded["host"]
                    if n == "dppasgd.round")
    boundary = starts[-1]
    last_op_end = max(b for a, b in ops if a < boundary)
    reads = [s + d for s, d, n, _ in recorded["host"]
             if n == "dppasgd.host_read" and s < boundary]
    assert reads and max(reads) >= last_op_end
    (batch,) = [s + d for s, d, n, _ in recorded["host"]
                if n == "dppasgd.batch" and s >= boundary]
    assert min(a for a, _ in ops if a >= boundary) >= batch


def test_device_lead_is_the_least_shift_after_every_enqueue():
    # run 1 starts 50 ns before its enqueue, run 2 20 ns; run 3 unpaired
    assert scopes.device_lead_ns({1: 100, 2: 500, 3: 7},
                                 {1: 150, 2: 520, 4: 1}) == 50
    # a device that starts after its enqueue needs no shift
    assert scopes.device_lead_ns({1: 200}, {1: 150}) == 0
    assert scopes.device_lead_ns({}, {}) == 0


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """Protobuf wire bytes of (field number, int | str | bytes | [int])."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
            continue
        if isinstance(value, list):             # packed repeated int64
            value = b"".join(_varint(v) for v in value)
        elif isinstance(value, str):
            value = value.encode()
        out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _inst(iid, name, opcode, op_name="", operands=(), called=()):
    fields = [(1, name), (2, opcode), (35, iid)]
    if op_name:
        fields.append((7, _msg((2, op_name))))
    if operands:
        fields.append((36, list(operands)))
    if called:
        fields.append((38, list(called)))
    return _msg(*fields)


def test_hlo_paths_give_loop_state_ops_the_names_they_fuse():
    """XLA names an instruction it builds around a loop's state after the
    loop (``.../while``): such a fusion stands for what it fuses, a copy
    for what it copies, and the loop itself for nothing."""
    loop = "jit(round_step)/while/body/closed_call/while"
    fused = _msg((1, "fused"), (5, 7),
                 (2, _inst(1, "p", "parameter", loop)),
                 (2, _inst(2, "mul", "multiply", CN + "/mul", [1])),
                 (2, _inst(3, "dus", "dynamic-update-slice", loop, [2])))
    entry = _msg((1, "entry"), (5, 8),
                 (2, _inst(4, "dot", "dot", LG + "/jvp()/dot_general")),
                 (2, _inst(5, "fusion.3", "fusion", loop, [4], [7])),
                 (2, _inst(6, "copy.9", "copy", loop, [5])),
                 (2, _inst(7, "while.1", "while", loop, [6])),
                 (2, _inst(8, "gte", "get-tuple-element", loop, [7])))
    paths = scopes.hlo_paths(memoryview(_msg((3, fused), (3, entry))))
    assert paths["dot"] == (LG + "/jvp()/dot_general",)
    assert paths["fusion.3"] == (CN + "/mul",)
    assert paths["copy.9"] == (CN + "/mul",)
    assert paths["while.1"] == () and paths["gte"] == ()
    assert scopes.carries(paths["copy.9"], "dppasgd.clip_noise")
