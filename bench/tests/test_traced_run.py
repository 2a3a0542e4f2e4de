"""A traced run takes each profiler slice in a job of its own, takes the
slices again while a device-trace metric of the cell reads nothing, and
reads every metric over all slices taken.

The profiler's slices are stood in for (the CPU has no device plane);
the rest of the run is the harness's own, at the CPU size.
"""
import pytest

from bench import devtrace, harness
from bench.tests.test_correctness import CPU, small_cell


def _slice(*modules):
    return devtrace.Slice(device="d", window_ns=1e8, busy_ns=9e7,
                          modules={m: [2e7, 2e7] for m in modules})


BOTH = ("jit__cluster_chunk_step", "jit__score_chunk")
FAST = {"degrees": 1e-4, "clustering": 1e-4, "mapping": 1e-4,
        "prepartition": 1e-4, "writeback": 1e-4, "scoring": 1e-4,
        "finalize": 1e-4}


@pytest.mark.parametrize("taken, jobs, reads", [
    ([[_slice(*BOTH)]], 1, True),
    ([[], [_slice(*BOTH)]], 2, True),
    ([[_slice(BOTH[0])], [_slice(BOTH[1])]], 2, True),
    ([[], [], [], []], 1 + harness.TRACE_RETRIES, False),
], ids=["first", "after_empty", "split", "never"])
def test_slices_again_until_every_trace_metric_reads(taken, jobs, reads,
                                                    tmp_path, monkeypatch):
    calls = []

    def traced_jobs(cell, graph, work_dir, marks):
        assert [p for p, _ in marks] and all(n > 0 for _, n in marks)
        calls.append(marks)
        # the traced jobs ran every stage faster than the window's
        return list(taken[len(calls) - 1]), [{"timings_s": FAST}]

    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    monkeypatch.setattr(harness, "_traced_jobs", traced_jobs)
    res = harness.run_cell(small_cell("rmat20-k256.2psl"), 2**31 + 5, 0.0,
                           True, 0.0, CPU)
    assert res["correct"] and len(calls) == jobs
    # taken again, the slices are placed by the traced jobs' times too
    fast = harness._trace_marks({"timings_s": FAST},
                                small_cell("rmat20-k256.2psl")
                                .traffic["trace_slices"])
    assert all(m == fast for m in calls[1:]) and calls[0] != fast
    rooflines = {"cluster_step_roofline", "score_chunk_roofline"}
    assert (rooflines <= set(res["metrics"])) is reads
    assert rooflines.isdisjoint(res["metrics"]) is not reads
    if reads:
        assert res["metrics"]["device_idle_share"]["value"] == \
            pytest.approx(10.0)
        slices = sum(len(t) for t in taken[:jobs])
        assert res["device"]["window_s"] == pytest.approx(0.1 * slices)


def test_one_job_to_each_slice(tmp_path, monkeypatch):
    """Collecting one slice must not push the next past its phase: each
    mark gets a tracer and a job of its own, started together."""
    events = []

    class Tracer:
        def __init__(self, marks):
            self.marks, self.began, self.ended = marks, [0.0], [0.1]

        def start(self):
            events.append(("start", self.marks))

        def join(self):
            events.append(("join", self.marks))
            return [self.marks]

    def run_job(argv):
        events.append(("job", argv[argv.index("--artifact-dir") + 1]))
        return {"timings_s": {}}

    monkeypatch.setattr(devtrace, "SliceTracer", Tracer)
    monkeypatch.setattr(devtrace, "reduce_xspace",
                        lambda marks: [_slice(str(marks))])
    monkeypatch.setattr(harness, "run_job", run_job)
    marks = [(0.5, 0.1), (5.0, 0.1)]
    cell = small_cell("rmat20-k256.2psl")
    got, reports = harness._traced_jobs(cell, "g.bin", str(tmp_path),
                                        marks)
    assert reports == [{"timings_s": {}}] * 2
    art = str(tmp_path / "traced")
    assert events == [("start", [marks[0]]), ("job", art),
                      ("join", [marks[0]]), ("start", [marks[1]]),
                      ("job", art), ("join", [marks[1]])]
    assert [list(s.modules) for s in got] == [[str([m])] for m in marks]


def test_a_stall_in_one_job_does_not_move_the_slices():
    """Each stage takes the least time any job gave it: a window job
    that stalled 1.6 s in clustering leaves the marks where the other
    job puts them."""
    even = {"degrees": 0.2, "clustering": 1.02, "mapping": 0.3,
            "prepartition": 1.38, "writeback": 0.49, "scoring": 3.48}
    stalled = dict(even, clustering=2.62, scoring=3.5)
    slices = small_cell("rmat20-k256.2psl").traffic["trace_slices"]
    fastest = harness._fastest([{"timings_s": stalled},
                                {"timings_s": even}])
    assert fastest == {"timings_s": even}
    assert list(fastest["timings_s"]) == list(even)     # stage order kept
    assert harness._trace_marks(fastest, slices) == [
        (pytest.approx(0.66), 0.1), (pytest.approx(5.08), 0.1)]
