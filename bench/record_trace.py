#!/usr/bin/env python3
"""Record the small trace that the reduction's tests read: one traced
round of a cell on the chip, cut to a slice around the first two
``dp_clip_noise`` kernel calls, in ``xtrace``'s compact form.

    python3 bench/record_trace.py --workload <cell> --out bench/testdata/trace_small.json

The benchmark's own runs do not run this.
"""
import argparse
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import catalog  # noqa: E402
import harness  # noqa: E402
import xtrace  # noqa: E402

SLICE_NS = 40_000_000


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(catalog.ROOT / "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir())
    cell = catalog.cell(args.workload)
    _, spec, state, traffic, make_weights, key = harness.build(cell, args.seed)
    state, _ = harness.checked_rounds(spec, state, traffic, make_weights, key)
    trace_dir = catalog.ROOT / ".bench_out" / "record"
    shutil.rmtree(trace_dir, ignore_errors=True)
    harness.window(spec, state, traffic, 60.0, 1, trace_dir)
    trace = xtrace.load(str(sorted(trace_dir.rglob("*.xplane.pb"))[-1]))
    shutil.rmtree(trace_dir, ignore_errors=True)
    dev = sorted(trace["devices"])[0]
    first = min(s for s, _, n, k in trace["devices"][dev]
                if k == "custom" and n == "dp_clip_noise")
    lo, hi = first - SLICE_NS // 4, first + SLICE_NS
    keep = lambda s, d: s < hi and s + d > lo
    small = {"window_ns": [lo, hi],
             "devices": {dev: [e for e in trace["devices"][dev]
                               if keep(e[0], e[1])]},
             "host": [e for e in trace["host"] if keep(e[0], e[1])]}
    xtrace.save(small, args.out)
    print(f"{args.out}: {len(small['devices'][dev])} device events, "
          f"{len(small['host'])} host events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
