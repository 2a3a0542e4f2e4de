"""One run of one cell: set-up, the measured window, the check, the result.

Set-up generates the cell's graph from the seed, writes it as the paper's
binary edge list, and warms up with one whole job over it through the
partition CLI (``repro.launch.partition.main``), so every program the
window runs is compiled or loaded from the persistent cache.  The window
then runs the same job back to back, one at a time, until ``seconds``
have passed and the running job has finished.  A compilation or cache
load inside the window fails the run.  After the window, the peak device
memory is read, a traced run traces slices of more jobs, one slice to a
job, and the last artifact is judged against the plain reference.

Each slice sits at a set time into its job: the middle of its stage by
the least time the window's jobs gave each stage, so a stall in one of
them does not move it.  A stall in the traced job can still put a slice
where the device runs nothing the cell's readers look for: where a
device-trace metric of the cell reads nothing, the slices are taken
again, placed by the traced jobs' stage times too, up to
``TRACE_RETRIES`` more times; the metrics are then read over all slices
taken.
"""
from __future__ import annotations

import contextlib
import gc
import io
import json
import os
import shutil
import sys
import time
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

from . import check, devtrace
from .graphs import generate, stream_order

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK_DIR = os.path.join(ROOT, ".bench_work")
#: times the slices are taken again at most where a device-trace metric
#: read nothing
TRACE_RETRIES = 2


class NoDevice(RuntimeError):
    """No accelerator of the peaks table, or fewer chips than the cell."""


def load_peaks() -> dict:
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        return json.load(f)


def find_device(chips: int) -> dict:
    """The accelerator JAX sees, with its entry of the peaks table."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform == "cpu":
        raise NoDevice("JAX finds no accelerator, only the CPU")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{len(devices)}")
    peaks = load_peaks()["devices"]
    if d.device_kind not in peaks:
        raise NoDevice(f"device kind {d.device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(peaks)})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "peaks": peaks[d.device_kind]}


class CompileCounter:
    """Counts XLA compilations (a persistent-cache load counts too)."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def peak_bytes() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def job_argv(cell, graph: str, art_dir: str) -> list:
    """The partition CLI's arguments: the fixed ones, then the traffic's
    ``cli`` flags in their order (``true`` gives a bare flag)."""
    t = cell.traffic
    argv = ["--input", graph, "--k", str(cell.config["k"]),
            "--algorithm", t["algorithm"], "--artifact-dir", art_dir,
            "--no-plan", "--alpha", str(cell.config["alpha"]),
            "--chunk-size", str(t["chunk"])]
    for flag, value in t.get("cli", {}).items():
        argv += [f"--{flag}"] if value is True else [f"--{flag}", str(value)]
    return argv


def run_job(argv: list) -> dict:
    """One partition job through the CLI; its report."""
    from repro.launch import partition
    with contextlib.redirect_stdout(io.StringIO()):
        return partition.main(argv)


@dataclass
class LayerContext:
    """What a per-layer metric reader may read."""

    jobs: list                     # CLI reports of the window's jobs
    slices: list                   # devtrace.Slice of the traced job
    config: dict
    traffic: dict
    peaks: dict
    facts: dict = field(default_factory=dict)

    def mean_timing(self, stage: str) -> float | None:
        vals = [j["timings_s"][stage] for j in self.jobs
                if stage in j.get("timings_s", {})]
        return float(np.mean(vals)) if vals else None

    def body(self, module: str) -> tuple[int, float]:
        """(whole executions, device seconds) of a jitted program."""
        durs = [d for s in self.slices for d in s.modules.get(module, [])]
        return len(durs), sum(durs) / 1e9


def _fastest(reports: list) -> dict:
    """A report whose every stage took the least time any of ``reports``
    gave it: where one job stalled in a stage, the others set it."""
    stages = {}
    for r in reports:
        for stage, secs in r["timings_s"].items():
            stages[stage] = min(secs, stages.get(stage, secs))
    return {"timings_s": stages}


def _trace_marks(report: dict, slices: list) -> list:
    """(offset, length) of each slice: the middle of each named stage of
    the job, placed by the stage times of a window job."""
    t = report["timings_s"]
    start, at = 0.0, {}
    for stage, secs in t.items():
        at[stage] = start + secs / 2
        start += secs
    return [(max(0.0, at[s["phase"]] - s["seconds"] / 2), s["seconds"])
            for s in slices if s["phase"] in at]


def _traced_jobs(cell, graph: str, work_dir: str, marks: list) -> list:
    """The ``devtrace.Slice``s at ``marks``, each taken in a job of its
    own (collecting a slice of the clustering scan takes the profiler 4-7 s
    on a v5e, past the next mark of the same job), and the jobs' reports."""
    trace_art = os.path.join(work_dir, "traced")
    slices, reports = [], []
    for offset, length in marks:
        tracer = devtrace.SliceTracer([(offset, length)])
        tracer.start()
        report = run_job(job_argv(cell, graph, trace_art))
        (xs,) = tracer.join()
        got = devtrace.reduce_xspace(xs)
        runs = Counter()
        for s in got:
            runs.update({m: len(d) for m, d in s.modules.items()})
        say(f"trace: slice of {length} s planned at {offset:.3f} s began "
            f"at {tracer.began[0]:.3f} s, collected at {tracer.ended[0]:.3f}"
            f" s: {sum(s.window_ns for s in got) / 1e9:.4f} s of device "
            f"window, whole executions {dict(runs)}; the job's stages "
            f"{report['timings_s']}")
        slices += got
        reports.append(report)
        shutil.rmtree(trace_art, ignore_errors=True)
    return slices, reports


def _breakdown(slices: list) -> dict:
    ops, gaps = {}, {}
    for s in slices:
        for name, ns in s.ops.items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9
        for name, ns in s.gaps.items():
            gaps[name] = gaps.get(name, 0.0) + ns / 1e9
    top = lambda d: [[k, v] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(ops), "idle_gaps": top(gaps)}


def say(*args) -> None:
    print(*args, flush=True)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict) -> dict:
    """The result line of one run (see module docstring)."""
    work_dir = os.path.join(WORK_DIR, cell.name)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    try:
        return _run(cell, seed, seconds, trace, t_start, device, work_dir)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def _run(cell, seed, seconds, trace, t_start, device, work_dir) -> dict:
    t0 = time.perf_counter()
    edges = stream_order(generate(cell.config, seed),
                         cell.traffic["order"], seed)
    graph = os.path.join(work_dir, "graph.bin")
    edges.tofile(graph)
    E, V = len(edges), int(edges.max()) + 1
    t1 = time.perf_counter()
    say(f"set-up: {cell.config_name} seed {seed}: {E} edges, {V} vertices, "
        f"generated and written in {t1 - t0:.3f} s")
    compiles = CompileCounter()
    art = os.path.join(work_dir, "artifact")
    argv = job_argv(cell, graph, art)
    gen_peak = peak_bytes()
    run_job(argv)
    setup_peak = peak_bytes()
    say(f"set-up: warm-up job {time.perf_counter() - t1:.3f} s, "
        f"{compiles.count} compilations or cache loads; peak_bytes_in_use "
        f"{gen_peak} before it, {setup_peak} after it")

    jobs, digests = [], []
    before = compiles.count
    t_w = time.perf_counter()
    setup_s = t_w - t_start
    while True:
        jobs.append(run_job(argv))
        done = time.perf_counter() - t_w
        digests.append(check.recorded_digest(art))
        if done >= seconds:
            break
    window_s = time.perf_counter() - t_w
    in_window = compiles.count - before
    gc.collect()
    peak = peak_bytes()
    say(f"window: {len(jobs)} jobs in {window_s:.3f} s; compilations in "
        f"the window: {in_window}; peak_bytes_in_use after the window "
        f"{peak}: the partition job sets it (set-up puts nothing on the "
        f"device but one job like the window's)")

    slices, traced = [], []
    if trace:
        marks = _trace_marks(_fastest(jobs), cell.traffic["trace_slices"])
        slices, traced = _traced_jobs(cell, graph, work_dir, marks)

    from repro.core import PartitionArtifact
    t_c = time.perf_counter()
    checks, facts = check.judge(edges, cell.config, cell.traffic,
                                cell.limits, art, digests, jobs[-1],
                                PartitionArtifact.load)
    checks = {"compiles_in_window": {"value": in_window, "limit": 0},
              **checks}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    say(f"check: reference comparison {time.perf_counter() - t_c:.3f} s")

    dev = {k: device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = peak
    metrics = {}
    if not trace:
        values = {"edges_per_s": E * len(jobs) / window_s,
                  "replication_factor": facts.get("replication_factor"),
                  "peak_device_bytes": peak, "setup_s": setup_s}
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        ctx = LayerContext(jobs=jobs, slices=slices, config=cell.config,
                           traffic=cell.traffic, peaks=device["peaks"],
                           facts=facts)
        values = {m["name"]: read(ctx) for m, read in cell.per_layer}
        for _ in range(TRACE_RETRIES):
            missing = [m["name"] for m, _ in cell.per_layer
                       if m["source"] == "device_trace"
                       and values[m["name"]] is None]
            if not missing:
                break
            say(f"trace: {', '.join(missing)} read nothing from "
                f"{len(slices)} slices; taking them again")
            marks = _trace_marks(_fastest(jobs + traced),
                                 cell.traffic["trace_slices"])
            more, reports = _traced_jobs(cell, graph, work_dir, marks)
            slices += more
            traced += reports
            values = {m["name"]: read(ctx) for m, read in cell.per_layer}
        for m, _ in cell.per_layer:
            if values[m["name"]] is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        chips = max(len({s.device for s in slices}), 1)
        dev["busy_s"] = sum(s.busy_ns for s in slices) / 1e9 / chips
        dev["window_s"] = sum(s.window_ns for s in slices) / 1e9 / chips
    for name, c in checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} <= {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": len(jobs),
              "failed": 0 if correct else len(jobs),
              "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = _breakdown(slices)
    result["checks"] = checks
    return result

