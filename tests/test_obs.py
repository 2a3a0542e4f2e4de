"""repro.obs: span tracing, stall attribution, metrics, exporters — and
their engine integration (bit-identity, disjoint timings, CLI --trace)."""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import (InMemoryEdgeStream, MemmapEdgeStream, SPEC_REGISTRY,
                        run_spec, spec_for)
from repro.obs import (NULL_REGISTRY, NULL_TRACER, MetricsRegistry,
                       PipelineStallReport, STAGES, TraceValidationError,
                       Tracer, chrome_trace, get_registry, get_tracer,
                       trace_summary_table, use_registry, use_tracer,
                       validate_chrome_trace, write_chrome_trace)

from conftest import tspec

ALL_ALGOS = sorted(SPEC_REGISTRY)


@pytest.fixture(scope="module")
def seed_graph():
    rng = np.random.default_rng(7)
    e = rng.integers(0, 300, (3000, 2)).astype(np.int32)
    return e[e[:, 0] != e[:, 1]]


def _spans(events, name=None):
    return [ev for ev in events
            if ev["ph"] == "X" and (name is None or ev["name"] == name)]


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_same_thread():
    tr = Tracer()
    with tr.span("outer", cat="t"):
        with tr.span("inner", cat="t", chunk=3):
            pass
    inner, outer = _spans(tr.events())
    assert (inner["name"], outer["name"]) == ("inner", "outer")
    # inner's [ts, ts+dur] interval nests inside outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["args"] == {"chunk": 3}


def test_spans_nest_across_threads_per_tid():
    """Each thread gets its own lane (tid); spans opened/closed on a
    thread nest within that lane even while another thread traces."""
    tr = Tracer()
    done = threading.Event()

    def worker():
        with tr.span("worker_outer"):
            with tr.span("worker_inner"):
                done.set()

    with tr.span("main_outer"):
        t = threading.Thread(target=worker, name="obs-worker")
        t.start()
        t.join()
    assert done.is_set()
    spans = _spans(tr.events())
    tids = {ev["tid"] for ev in spans}
    assert len(tids) == 2
    for tid in tids:                       # proper nesting per lane
        lane = sorted((ev for ev in spans if ev["tid"] == tid),
                      key=lambda ev: ev["ts"])
        for a, b in zip(lane, lane[1:]):
            ends_before = a["ts"] + a["dur"] <= b["ts"] + 1e-6
            contains = (a["ts"] <= b["ts"] + 1e-6 and
                        b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 1e-6)
            contained = (b["ts"] <= a["ts"] + 1e-6 and
                         a["ts"] + a["dur"] <= b["ts"] + b["dur"] + 1e-6)
            assert ends_before or contains or contained
    # thread_name metadata recorded once per lane
    meta = [ev for ev in tr.events() if ev["ph"] == "M"]
    assert {ev["tid"] for ev in meta} == tids
    assert "obs-worker" in {ev["args"]["name"] for ev in meta}


def test_complete_records_retrospective_span():
    tr = Tracer()
    tr.complete("read", "prefetch", 0.25, chunk=0)
    (ev,) = _spans(tr.events())
    assert ev["dur"] == pytest.approx(0.25e6, rel=1e-3)
    assert ev["cat"] == "prefetch" and ev["ts"] >= 0


def test_tracer_event_cap_counts_drops():
    tr = Tracer(max_events=3)
    for i in range(10):
        tr.complete("x", duration_s=0.0, i=i)
    assert len(tr.events()) == 3          # thread meta + 2 spans
    assert tr.dropped == 8


def test_active_tracer_stack_and_null_default():
    assert get_tracer() is NULL_TRACER
    tr = Tracer()
    with use_tracer(tr):
        assert get_tracer() is tr
        with use_tracer(None):            # None degrades to the null tracer
            assert get_tracer() is NULL_TRACER
        assert get_tracer() is tr
    assert get_tracer() is NULL_TRACER
    # the null tracer's spans still time and credit a stall clock, and
    # it records nothing
    clk = obs.StallClock()
    with NULL_TRACER.span("engine.fold", stall=clk, chunk=0) as span:
        pass
    assert span.seconds >= 0
    assert clk.seconds == {"fold": span.seconds}
    NULL_TRACER.complete("a", "c", 1.0)
    assert NULL_TRACER.events() == [] and NULL_TRACER.dropped == 0


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_registry_instruments():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.0)
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(1e-3)
    reg.histogram("h").observe(3e-3)
    snap = reg.snapshot()
    assert snap["c"] == {"type": "counter", "value": 5}
    assert snap["g"]["value"] == 1.0 and snap["g"]["max"] == 2.0
    assert snap["h"]["count"] == 2
    assert snap["h"]["mean"] == pytest.approx(2e-3)
    json.dumps(snap)                      # JSON-safe by contract
    with pytest.raises(TypeError):
        reg.gauge("c")                    # type conflict on the same name


def test_null_registry_is_inert():
    assert get_registry() is NULL_REGISTRY
    NULL_REGISTRY.counter("x").inc()
    NULL_REGISTRY.gauge("x").set(1)
    NULL_REGISTRY.histogram("x").observe(1)
    assert NULL_REGISTRY.snapshot() == {}
    reg = MetricsRegistry()
    with use_registry(reg):
        assert get_registry() is reg
    assert get_registry() is NULL_REGISTRY


# ---------------------------------------------------------------------------
# stall report
# ---------------------------------------------------------------------------

def test_stall_report_roundtrip_and_fractions():
    clk = obs.StallClock()
    clk.add("engine.read", 0.1)
    clk.add("engine.dispatch", 0.5)
    clk.add("engine.device_wait", 0.05)
    clk.add("engine.fetch", 0.05)
    clk.add("engine.write", 0.04)
    clk.add("engine.fold", 0.06)
    clk.add("engine.queue_wait", 0.05)
    rep = PipelineStallReport(passes=[clk.report("scoring")])
    d = rep.to_dict()
    assert d["critical_stage"] == "dispatch"
    # writeback is busy waiting on the device, fetching, writing, folding
    assert d["stages"]["writeback"]["busy_s"] == pytest.approx(0.2)
    assert d["stages"]["writeback"]["chunks"] == 1
    assert d["passes"][0]["attribution"]["queue_wait"] == 0.05
    assert rep.stage_seconds()["scoring"]["fold"] == 0.06
    assert d["verdict"].startswith("dispatch-bound")
    for st in d["stages"].values():
        assert st["busy_frac"] + st["idle_frac"] == pytest.approx(1.0)
        assert 0.0 <= st["busy_frac"] <= 1.0
    back = PipelineStallReport.from_dict(json.loads(json.dumps(d)))
    for s, st in back.to_dict()["stages"].items():
        assert st == pytest.approx(d["stages"][s])
    assert back.critical_stage == "dispatch"
    # summary table renders every stage and the verdict
    table = trace_summary_table(d)
    assert "dispatch" in table and "verdict" in table


# ---------------------------------------------------------------------------
# chrome trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_roundtrip_and_schema(tmp_path):
    tr = Tracer()
    with tr.span("outer", cat="t"):
        tr.complete("read", "prefetch", 0.01)
    tr.instant("marker")
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, tr, metadata={"k": 4})
    doc = json.load(open(path))
    assert doc["otherData"]["k"] == 4
    names = validate_chrome_trace(doc)
    assert names == {"outer", "read"}     # X spans only
    phases = {ev["ph"] for ev in doc["traceEvents"]}
    assert phases == {"X", "i", "M"}


@pytest.mark.parametrize("mutate", [
    lambda doc: doc.pop("traceEvents"),
    lambda doc: doc["traceEvents"].clear(),
    lambda doc: doc["traceEvents"][0].update(ph="Z"),
    lambda doc: doc["traceEvents"][-1].update(name=""),
    lambda doc: doc["traceEvents"][-1].pop("pid"),
    lambda doc: doc["traceEvents"][-1].update(ts=-5),
    lambda doc: doc["traceEvents"][-1].update(dur=None),
])
def test_chrome_trace_validation_rejects(mutate):
    tr = Tracer()
    with tr.span("s"):
        pass
    doc = chrome_trace(tr)
    mutate(doc)
    with pytest.raises(TraceValidationError):
        validate_chrome_trace(doc)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_ALGOS)
def test_traced_run_bit_identical_all_specs(name, seed_graph):
    """Tracing only observes the pipeline: assignment and quality match an
    untraced run exactly, and the stall report is well-formed."""
    k = 8
    spec = tspec(name)
    plain = run_spec(spec, InMemoryEdgeStream(seed_graph), k)
    tracer, reg = Tracer(), MetricsRegistry()
    traced = run_spec(spec, InMemoryEdgeStream(seed_graph), k,
                      tracer=tracer, metrics=reg)
    np.testing.assert_array_equal(np.asarray(plain.assignment),
                                  np.asarray(traced.assignment))
    assert (plain.quality.replication_factor
            == traced.quality.replication_factor)
    assert plain.quality.balance == traced.quality.balance

    stall = traced.extras["stall_report"]
    assert stall["critical_stage"] in STAGES
    for st in stall["stages"].values():
        assert st["busy_frac"] + st["idle_frac"] == pytest.approx(1.0)
    names = validate_chrome_trace(chrome_trace(tracer))
    assert {"engine.read", "engine.queue_wait", "engine.dispatch",
            "engine.device_wait", "engine.fetch",
            "engine.write"} <= names      # every pipeline stage covered
    assert any(n.startswith("engine.pass.") for n in names)
    assert names <= set(obs.SPANS)
    snap = reg.snapshot()
    assert snap["engine.edges_streamed"]["value"] > 0
    assert snap["engine.chunks_in_flight"]["max"] >= 1


def test_disabled_tracer_adds_no_extras_keys(seed_graph):
    spec = spec_for("2psl", chunk_size=512)
    res = run_spec(spec, InMemoryEdgeStream(seed_graph), 4)
    assert "stall_report" not in res.extras
    res2 = run_spec(spec, InMemoryEdgeStream(seed_graph), 4,
                    tracer=NULL_TRACER, metrics=NULL_REGISTRY)
    assert set(res.extras) == set(res2.extras)


def test_prefetch_thread_spans_land_in_same_trace(tmp_path, seed_graph):
    """At depth >= 2 the read spans come from the prefetch thread — a
    different tid than the dispatch spans, in the same trace document."""
    path = str(tmp_path / "g.bin")
    np.ascontiguousarray(seed_graph, dtype=np.uint32).tofile(path)
    spec = spec_for("hdrf", chunk_size=512, pipeline_depth=3)
    tracer = Tracer()
    run_spec(spec, MemmapEdgeStream(path), 4, tracer=tracer)
    reads = _spans(tracer.events(), "engine.read")
    dispatches = _spans(tracer.events(), "engine.dispatch")
    assert reads and dispatches
    assert {ev["tid"] for ev in reads}.isdisjoint(
        {ev["tid"] for ev in dispatches})
    # chunk indices line up 1:1 between the stages; the one read more is
    # the read that finds the stream's end
    read_chunks = sorted(ev["args"]["chunk"] for ev in reads)
    dispatched = sorted(ev["args"]["chunk"] for ev in dispatches)
    assert read_chunks == dispatched + [len(dispatched)]


def test_timings_disjoint_writeback_and_finalize(seed_graph):
    """Satellite: timings keys are disjoint phases — writeback is its own
    key (not absorbed into scoring at depth 1) and total_seconds is the
    plain sum."""
    spec = spec_for("2psl", chunk_size=512, pipeline_depth=1)
    res = run_spec(spec, InMemoryEdgeStream(seed_graph), 4)
    assert {"degrees", "clustering", "mapping", "prepartition", "scoring",
            "writeback", "finalize"} <= set(res.timings)
    assert res.timings["writeback"] >= 0
    assert res.total_seconds == pytest.approx(
        sum(res.timings.values()) + res.simulated_io_seconds)
    # phases partition the run wall clock: no key is double-counted, so
    # the sum cannot exceed a wall-clock measurement around the run —
    # checked structurally: every value is non-negative
    assert all(v >= -1e-9 for v in res.timings.values())


def test_artifact_manifest_carries_stall_report(tmp_path, seed_graph):
    from repro.core import PartitionArtifact
    spec = spec_for("dbh", chunk_size=1024)
    stream = InMemoryEdgeStream(seed_graph)
    res = run_spec(spec, stream, 4, tracer=Tracer())
    art = PartitionArtifact.save(
        str(tmp_path / "art"), res, num_vertices=stream.num_vertices,
        num_edges=stream.num_edges)
    manifest = json.load(open(str(tmp_path / "art/manifest.json")))
    assert manifest["stall_report"]["critical_stage"] in STAGES
    # untraced runs persist an explicit null, not a missing key
    res2 = run_spec(spec, stream, 4)
    PartitionArtifact.save(
        str(tmp_path / "art2"), res2, num_vertices=stream.num_vertices,
        num_edges=stream.num_edges)
    manifest2 = json.load(open(str(tmp_path / "art2/manifest.json")))
    assert manifest2["stall_report"] is None


def test_partition_cli_trace_end_to_end(tmp_path, seed_graph, capsys):
    from repro.launch.partition import main
    path = str(tmp_path / "g.bin")
    np.ascontiguousarray(seed_graph, dtype=np.uint32).tofile(path)
    trace_path = str(tmp_path / "trace.json")
    main(["--input", path, "--k", "4", "--algorithm", "2psl",
          "--chunk-size", "512", "--trace", trace_path,
          "--trace-summary", "--json"])
    out = capsys.readouterr()
    rep = json.loads(out.out)
    assert rep["trace"] == trace_path
    assert rep["critical_stage"] in STAGES
    assert "verdict" in out.err           # summary table on stderr (--json)
    doc = json.load(open(trace_path))
    names = validate_chrome_trace(doc)
    assert {"engine.read", "engine.dispatch", "engine.fetch",
            "engine.write"} <= names
    assert doc["otherData"]["spec"]["algorithm"] == "2psl"
    assert doc["otherData"]["metrics"]["engine.edges_streamed"]["value"] > 0


# ---------------------------------------------------------------------------
# spans in the profiler's trace, stage seconds, clustering counters
# ---------------------------------------------------------------------------

def _cli(argv):
    import contextlib
    import io
    from repro.launch.partition import main
    with contextlib.redirect_stdout(io.StringIO()):
        return main(argv)


def _graph_file(tmp_path, edges):
    path = str(tmp_path / "g.bin")
    np.ascontiguousarray(edges, dtype=np.uint32).tofile(path)
    return path


def _profiled(fn):
    """Run ``fn`` inside a profiler session opened as the benchmark's
    slices open it (Python tracer off) -> (fn's result, host lines as
    ``[[(name, start_ns, end_ns, stats)]]``)."""
    import jax
    from jax._src.lib import _profiler
    jax.devices()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    session = _profiler.ProfilerSession(options)
    try:
        out = fn()
    finally:
        xspace = session.stop()
    data = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    lines = [[(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats)) for ev in line.events]
             for plane in data.planes if plane.name.startswith("/host")
             for line in plane.lines]
    return out, lines


def test_every_span_reaches_the_profiler_trace(tmp_path, seed_graph):
    """2PS-L (checkpointed, then resumed) and DBH jobs through the CLI,
    and one run of each other pass, inside a profiler session: every name
    of the vocabulary is on the host plane, every ``engine.*`` or
    ``artifact.*`` name there is in it, and the chunk spans sit on the
    thread that runs them, named by stage alone."""
    path = _graph_file(tmp_path, seed_graph)
    base = ["--input", path, "--k", "4", "--chunk-size", "512",
            "--no-plan"]

    def jobs():
        _cli(base + ["--algorithm", "2psl", "--artifact-dir",
                     str(tmp_path / "a"), "--checkpoint-every", "2"])
        _cli(base + ["--algorithm", "2psl", "--artifact-dir",
                     str(tmp_path / "a"), "--resume"])
        _cli(base + ["--algorithm", "dbh", "--artifact-dir",
                     str(tmp_path / "b")])
        for name in ("hep", "buffered"):
            run_spec(tspec(name), InMemoryEdgeStream(seed_graph), 4)

    _, lines = _profiled(jobs)
    names = {ev[0] for line in lines for ev in line}
    ours = {n for n in names if n.startswith(("engine.", "artifact."))}
    assert ours == set(obs.SPANS)
    main = max(lines, key=lambda line: sum(
        ev[0] == "engine.dispatch" for ev in line))
    chunk_stages = {"engine." + s for s in obs.CHUNK_STAGES}
    on_main = {ev[0] for ev in main if ev[0] in chunk_stages}
    assert on_main == chunk_stages - {"engine.read"}
    # at the CLI's pipeline depth of 2 the prefetch thread reads
    reads = [ev for line in lines if line is not main for ev in line
             if ev[0] == "engine.read"]
    assert reads and not any(ev[0] == "engine.read" for ev in main)
    # per-chunk data rides as stats; the same chunk index ties stages
    folds = [ev for ev in main if ev[0] == "engine.fold"]
    assert folds and all("chunk" in ev[3] for ev in folds)
    assert ({ev[3]["chunk"] for ev in folds}
            <= {ev[3]["chunk"] for ev in reads})


@pytest.mark.parametrize("algorithm", ["2psl", "dbh"])
def test_untraced_cli_report_has_stage_seconds(tmp_path, seed_graph,
                                               algorithm):
    """Every report carries each pass's stage seconds and the artifact's
    save time, tracing off; fetch + write + fold is the writeback time."""
    path = _graph_file(tmp_path, seed_graph)
    rep = _cli(["--input", path, "--k", "4", "--chunk-size", "512",
                "--algorithm", algorithm, "--artifact-dir",
                str(tmp_path / "art"), "--no-plan"])
    assert "trace" not in rep and "critical_stage" not in rep
    phases = {"2psl": ["prepartition", "scoring"], "dbh": ["hashing"]}
    assert list(rep["stages_s"]) == phases[algorithm]
    for sec in rep["stages_s"].values():
        assert set(sec) <= set(obs.CHUNK_STAGES)
        assert {"read", "queue_wait", "dispatch", "device_wait", "fetch",
                "write"} <= set(sec)
        assert all(v >= 0 for v in sec.values())
    # the hashing and pre-partitioning passes fold replicas on the host
    assert "fold" in rep["stages_s"][phases[algorithm][0]]
    host = sum(sec.get(s, 0.0) for sec in rep["stages_s"].values()
               for s in ("fetch", "write", "fold"))
    assert host == pytest.approx(rep["timings_s"]["writeback"], abs=1e-3)
    assert rep["artifact_save_s"] > 0


def test_assignments_identical_traced_untraced_and_profiled(seed_graph):
    spec = spec_for("2psl", chunk_size=512)
    plain = run_spec(spec, InMemoryEdgeStream(seed_graph), 4)
    traced = run_spec(spec, InMemoryEdgeStream(seed_graph), 4,
                      tracer=Tracer())
    profiled, _ = _profiled(
        lambda: run_spec(spec, InMemoryEdgeStream(seed_graph), 4))
    for res in (traced, profiled):
        np.testing.assert_array_equal(np.asarray(plain.assignment),
                                      np.asarray(res.assignment))
        assert set(res.stages) == set(plain.stages)


def _numpy_clustering(edges, degrees, max_vol, chunk, sub):
    """Algorithm 1 in ``sub``-edge micro-batches over ``chunk``-edge
    chunks padded with invalid edges: every edge of a micro-batch reads
    the batch-entry state, and a vertex that several edges move goes with
    the last of them.  -> (v2c, vol, moves, active batches, batches)."""
    d = degrees.astype(np.int64)
    v2c = np.arange(len(d))
    vol = d.copy()
    moves = active = batches = 0
    for lo in range(0, len(edges), chunk):
        part = edges[lo:lo + chunk]
        for b in range(0, chunk, sub):
            e = part[b:b + sub]
            batches += 1
            last = {}
            for i, (u, v) in enumerate(e):
                cu, cv = v2c[u], v2c[v]
                if vol[cu] > max_vol or vol[cv] > max_vol:
                    continue
                if vol[cu] - d[u] <= vol[cv] - d[v]:
                    vs, cs, cl = u, cu, cv
                else:
                    vs, cs, cl = v, cv, cu
                if cs != cl and vol[cl] + d[vs] <= max_vol:
                    last[vs] = (cs, cl)
            for vs, (cs, cl) in last.items():
                v2c[vs] = cl
                vol[cl] += d[vs]
                vol[cs] -= d[vs]
            moves += len(last)
            active += bool(last)
    return v2c, vol, moves, active, batches


def test_cluster_counters_match_a_numpy_loop(seed_graph):
    from repro.core.clustering import streaming_clustering
    from repro.core.stream import compute_degrees
    stream = InMemoryEdgeStream(seed_graph)
    degrees = compute_degrees(stream, 512)
    max_vol = 2 * len(seed_graph) // 8
    res = streaming_clustering(stream, degrees, k=8, max_vol=max_vol,
                               chunk_size=512)
    v2c, vol, moves, active, batches = _numpy_clustering(
        seed_graph, degrees, max_vol, 512, 128)
    np.testing.assert_array_equal(res.v2c, v2c)
    np.testing.assert_array_equal(res.vol, vol)
    assert (res.moves, res.active_batches, res.batches) == (
        moves, active, batches)
    assert 0 < active < batches == 4 * -(-len(seed_graph) // 512)


def test_cluster_counters_reach_the_cli_report(tmp_path, seed_graph):
    path = _graph_file(tmp_path, seed_graph)
    rep = _cli(["--input", path, "--k", "8", "--chunk-size", "512",
                "--algorithm", "2psl", "--cluster-passes", "2"])
    _, _, moves, active, batches = _numpy_clustering(
        seed_graph, np.bincount(seed_graph.ravel(), minlength=300),
        2 * len(seed_graph) // 8, 512, 128)
    # two passes: the second starts from the first's tables
    assert rep["cluster_batches"] == 2 * batches
    assert rep["cluster_moves"] >= moves
    assert active <= rep["cluster_active_batches"] <= 2 * batches
    assert all(isinstance(rep[key], int) for key in (
        "cluster_moves", "cluster_active_batches", "cluster_batches"))


def test_reader_module_names_match_lowered_programs(monkeypatch):
    """The roofline readers look jitted programs up by their XLA module
    name; a renamed program must fail here, not read nothing."""
    import importlib.util
    import os

    import jax
    import jax.numpy as jnp
    from repro.core import partitioning as P
    from repro.core.clustering import _cluster_chunk_step

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)

    def reader_module(name):
        path = os.path.join(root, "bench", "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.MODULE

    def module_name(lowered):
        return lowered.compiler_ir().operation.attributes["sym_name"].value

    i32 = jax.ShapeDtypeStruct
    V, C, k = 64, 256, 8
    vec, edges, valid = (i32((V,), jnp.int32), i32((C, 2), jnp.int32),
                         i32((C,), jnp.bool_))
    cluster = _cluster_chunk_step.lower(
        vec, vec, vec, edges, valid, max_vol=10)
    score = P._score_chunk.lower(
        i32((V, 1), jnp.uint32), i32((k,), jnp.int32), vec, vec, vec,
        vec, edges, valid, k=k, cap=100)
    assert module_name(cluster) == reader_module("cluster_step_roofline")
    assert module_name(score) == reader_module("score_chunk_roofline")
    assert module_name(cluster) == "jit__cluster_chunk_step"
    assert module_name(score) == "jit__score_chunk"
