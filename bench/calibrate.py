"""Readings that a cell's correctness limit is set from, in one process.

    python -m bench.calibrate --workload <name> --seeds 11,12,13 [--out F]

For each seed: the cell's graph, one job through the timed path (the
partition CLI), and two readings of
``edges_vs_reference``: the program's artifact against the reference, and
the control against the reference.  The control is the reference with the
step a later change would be tempted to take, as the traffic's reference
file (``bench/references/<reference>.py``) defines it.  The limit lies
between the program's largest reading and the control's smallest.  Exits
2 without an accelerator, as ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, work_dir: str) -> dict:
    from bench import check, harness, registry
    from bench.graphs import generate, stream_order
    refs = registry.plugin("references", cell.traffic["reference"])
    edges = stream_order(generate(cell.config, seed),
                         cell.traffic["order"], seed)
    graph = os.path.join(work_dir, "graph.bin")
    edges.tofile(graph)
    art = os.path.join(work_dir, "artifact")
    t1 = time.perf_counter()
    report = harness.run_job(harness.job_argv(cell, graph, art))
    t2 = time.perf_counter()
    asg = np.fromfile(os.path.join(art, check.ASSIGNMENT), dtype=np.int32)
    ref = refs.assign(edges, cell.config, cell.traffic)
    t3 = time.perf_counter()
    ctl = refs.control(edges, cell.config, cell.traffic)
    t4 = time.perf_counter()
    return {"workload": cell.name, "seed": seed, "edges": len(edges),
            "program": check.mismatch_share(asg, ref),
            "control": check.mismatch_share(ctl, ref),
            "program_edges_differing": int(np.count_nonzero(asg != ref)),
            "replication_factor": report["replication_factor"],
            "job_s": t2 - t1, "reference_s": t3 - t2,
            "control_s": t4 - t3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    from bench import harness, registry
    from bench.run import _jax_cache
    cell = registry.cell(ROOT, args.workload)
    _jax_cache()
    try:
        harness.find_device(cell.chips)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    work_dir = os.path.join(harness.WORK_DIR, "calibrate-" + cell.name)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        shutil.rmtree(work_dir, ignore_errors=True)
        os.makedirs(work_dir)
        try:
            row = readings(cell, seed, work_dir)
        finally:
            shutil.rmtree(work_dir, ignore_errors=True)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    print(json.dumps({
        "workload": cell.name, "seeds": len(rows),
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
