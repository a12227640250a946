"""The reduction from trace to metrics, on the small trace recorded on a
v5e (``bench/testdata/trace_small.json``: one slice of an IoT round with
the first two ``dp_clip_noise`` calls) and on hand-made events."""
import numpy as np
import pytest

import catalog
import xtrace

SMALL = catalog.BENCH / "testdata" / "trace_small.json"


@pytest.fixture(scope="module")
def small():
    return xtrace.read(str(SMALL))


def brute_busy(events, lo, hi, step=1000):
    """Busy time by marking a grid of ``step``-ns cells: a second,
    independent count of the union."""
    grid = np.zeros(int((hi - lo) // step) + 1, bool)
    for s, d, *_ in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) // step):int(np.ceil((b - lo) / step))] = True
    return grid.sum() * step


def test_recorded_trace_names_the_kernel_and_the_window(small):
    (dev,) = small["devices"]
    kinds = {k for *_, k in small["devices"][dev]}
    assert {"container", "custom", "op"} <= kinds
    lo, hi = small["window_ns"]
    assert hi > lo
    ns, count = xtrace.kernel_ns(small, dev, "dp_clip_noise")
    want = [d for s, d, n, k in small["devices"][dev]
            if n == "dp_clip_noise" and k == "custom" and lo <= s < hi]
    # the norm pass and the scale+noise pass: two custom calls, one name
    assert count == len(want) >= 2
    assert ns == pytest.approx(sum(want))
    assert xtrace.kernel_ns(small, dev, "no_such_kernel") == (0.0, 0)


def test_busy_union_matches_a_grid_count(small):
    (dev,) = small["devices"]
    lo, hi = small["window_ns"]
    busy = xtrace.busy_ns(small, dev)
    assert 0 < busy <= hi - lo
    assert busy == pytest.approx(
        brute_busy(small["devices"][dev], lo, hi), abs=2000 * len(
            small["devices"][dev]))


def test_top_ops_and_gaps_come_from_the_window(small):
    ops = xtrace.top_ops(small)
    assert 1 <= len(ops) <= 10
    assert all(name not in xtrace.CONTAINERS for name, _ in ops)
    assert ops == sorted(ops, key=lambda x: -x[1])
    (dev,) = small["devices"]
    for name, secs in xtrace.idle_gaps(small, dev):
        assert isinstance(name, str) and secs > 0


def hand_trace():
    # device 0: a while loop around two ops, an all-reduce beside a
    # fusion, then another alone; a host round span over it all
    ev = [[0, 1000, "while", "container"],
          [0, 100, "fusion", "op"],
          [150, 100, "dp_clip_noise", "custom"],
          [300, 200, "all-reduce", "collective"],
          [300, 100, "fusion", "op"],
          [700, 100, "all-reduce", "collective"]]
    return {"window_ns": [0, 1000], "devices": {"TPU:0": ev},
            "host": [[0, 1000, "bench.window", "python3"],
                     [0, 1000, "bench.round", "python3"],
                     [550, 100, "train", "python3"]]}


def test_busy_union_and_kernel_time_on_hand_made_events():
    t = hand_trace()
    assert xtrace.busy_ns(t, "TPU:0") == 1000      # the while spans it all
    assert xtrace.kernel_ns(t, "TPU:0", "dp_clip_noise") == (100.0, 1)


def test_idle_gaps_name_the_host_span():
    t = hand_trace()
    ev = [e for e in t["devices"]["TPU:0"] if e[3] != "container"]
    t["devices"]["TPU:0"] = ev
    gaps = xtrace.idle_gaps(t, "TPU:0")
    # 500-700 under "train", 800-1000 under the round span alone, then
    # the two 50 ns gaps
    assert sorted(gaps[:2]) == [["bench.round", 200 / 1e9],
                                ["train", 200 / 1e9]]
    assert [s for _, s in gaps[2:]] == [50 / 1e9, 50 / 1e9]
    assert sum(s for _, s in gaps) == pytest.approx(
        (1000 - xtrace.busy_ns(t, "TPU:0")) / 1e9)


@pytest.mark.parametrize("text, want", [
    ("%dp_clip_noise.25 = f32[8,128]{1,0} custom-call(f32[2]{0} %p), "
     'custom_call_target="tpu_custom_call"', ("dp_clip_noise", "custom")),
    ("%while.492 = (s32[], bf16[2,8]) while((s32[], bf16[2,8]) %t)",
     ("while", "container")),
    ("%all-reduce.3 = f32[8] all-reduce(f32[8] %x), replica_groups={}",
     ("all-reduce", "collective")),
    ("%multiply_reduce_fusion.21 = (bf16[4]) fusion(bf16[4] %a)",
     ("multiply_reduce_fusion", "op")),
])
def test_classify_event_text(text, want):
    assert xtrace.classify(text) == want
