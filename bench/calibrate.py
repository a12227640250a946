#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process (the compiled programs are shared by every seed):

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \\
        --control 3 --faults 3 [--out readings.json]

- the program's numbers against the reference on each of ``--seeds``
  (the lower readings: sound runs);
- the control's, on the first ``--control`` seeds: the reference itself
  in the program's place at float8 (matmul inputs and bfloat16 leaves),
  the next precision below the configuration's bfloat16;
- each fault the cell can have, on the first ``--faults`` seeds, planted
  in the reference put in the program's place: half of each batch's rows
  left out (batch >= 2), the clients' average left out (clients >= 2),
  the largest leaf's clipped gradient reversed under its noise, and every
  clipped gradient dropped with the noise kept. A step that returns its
  state unchanged reads 1 in the two norm numbers by their definition.

The benchmark's own runs do not run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import catalog  # noqa: E402
import compare  # noqa: E402
import harness  # noqa: E402


def faults_of(cell) -> list[str]:
    out = []
    if int(cell.traffic["batch"]) >= 2:
        out.append("half_batch")
    if int(cell.traffic["clients"]) >= 2:
        out.append("no_exchange")
    return out + ["altered", "noise_only"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(catalog.ROOT / "src"))
    cell = catalog.cell(args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    refs = harness.References(cell)
    rows, base = [], None
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        model, spec, state, traffic, make_weights, key = harness.build(
            cell, seed, base)
        base = (model, spec, make_weights)
        state, program = harness.checked_rounds(spec, state, traffic,
                                                make_weights, key)
        del state
        ref = refs.record(seed, make_weights, key)
        readings = {"program": compare.numbers(program, ref)}
        variants = ((["control"] if i < args.control else [])
                    + (faults_of(cell) if i < args.faults else []))
        for v in variants:
            got = refs.record(seed, make_weights, key, v)
            readings[v] = compare.numbers(got, ref)
        row = {"seed": seed, "seconds": time.perf_counter() - t0,
               "losses": program["loss"], "ref_losses": ref["loss"],
               "gnorm": program["gnorm"], "ref_gnorm": ref["gnorm"],
               "readings": readings}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for kind in sorted({k for r in rows for k in r["readings"]}):
        got = [r["readings"][kind] for r in rows if kind in r["readings"]]
        pick = max if kind == "program" else min
        summary[kind] = {n: pick(g[n] for g in got) for n in compare.NAMES}
        summary[kind]["seeds"] = len(got)
    summary["memory_peak_bytes"] = harness.device_info(
        jax.devices(), cell.chips)["memory_peak_bytes"]
    print(json.dumps({"workload": cell.name, "summary": summary}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows,
                                              "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
