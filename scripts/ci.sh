#!/usr/bin/env bash
# Tier-1 CI entrypoint.
#
#   scripts/ci.sh              fast loop: CLI smoke stage + CPU backend
#                              pytest, slow SPMD subprocess tests excluded
#   scripts/ci.sh --full       CLI smoke stage + the complete tier-1 suite
#   scripts/ci.sh --multihost  fast loop + the opt-in multihost stage (the
#                              slow host-grouped SPMD subprocess tests:
#                              EGNN + GIN on 2 emulated hosts x 4 devices)
#
# Mode flags combine; extra args after them are forwarded to pytest.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

marker=(-m "not slow")
multihost=0
while [[ "${1:-}" == "--full" || "${1:-}" == "--multihost" ]]; do
    if [[ "$1" == "--full" ]]; then
        marker=()
    else
        multihost=1
    fi
    shift
done

# ---- CLI smoke stage: partition a tiny memmapped graph end-to-end into a
# PartitionArtifact, then reload assignment + cached halo plan ------------
smoke_dir=$(mktemp -d)
trap 'rm -rf "$smoke_dir"' EXIT
python - "$smoke_dir" <<'PY'
import sys
import numpy as np
rng = np.random.default_rng(0)
e = rng.integers(0, 64, (600, 2)).astype(np.uint32)
e = e[e[:, 0] != e[:, 1]]
e.tofile(sys.argv[1] + "/graph.bin")
PY
python -m repro.launch.partition \
    --input "$smoke_dir/graph.bin" --k 4 --algorithm 2psl \
    --chunk-size 256 --artifact-dir "$smoke_dir/artifact" --json \
    --trace "$smoke_dir/trace.json" \
    > "$smoke_dir/report.json"
python - "$smoke_dir" <<'PY'
import json, sys
import numpy as np
from repro.core import PartitionArtifact
report = json.load(open(sys.argv[1] + "/report.json"))
art = PartitionArtifact.load(sys.argv[1] + "/artifact")
asg = np.asarray(art.assignment)
assert len(asg) == art.num_edges and asg.min() >= 0 and asg.max() < art.k
plan = art.halo_plan()          # cached — reloads without the graph
assert plan.k == art.k == report["k"] == 4
assert plan.b_cap == report["b_cap"]
assert art.spec.algorithm == "2psl"
print(f"CLI smoke OK: rf={report['replication_factor']:.3f} "
      f"b_cap={plan.b_cap}")
PY

# ---- trace smoke stage: the --trace export from the CLI run above must be
# a valid Chrome trace_event doc covering every pipeline stage, and the
# manifest's stall report must name a critical stage with sane fractions --
python - "$smoke_dir" <<'PY'
import json, sys
from repro.obs import STAGES, validate_chrome_trace
doc = json.load(open(sys.argv[1] + "/trace.json"))
names = validate_chrome_trace(doc)
missing = {"engine.read", "engine.dispatch", "engine.device_wait",
           "engine.fetch", "engine.write"} - names
assert not missing, f"trace lacks pipeline-stage spans: {missing}"
assert any(n.startswith("engine.pass.") for n in names), names
manifest = json.load(open(sys.argv[1] + "/artifact/manifest.json"))
stall = manifest["stall_report"]
assert stall["critical_stage"] in STAGES, stall["critical_stage"]
for stage, st in stall["stages"].items():
    total = st["busy_frac"] + st["idle_frac"]
    assert abs(total - 1.0) < 1e-9, (stage, total)
print(f"trace smoke OK: {len(names)} span names, "
      f"critical stage {stall['critical_stage']} ({stall['verdict']})")
PY

# ---- bench smoke stage: engine throughput on a tiny graph, then validate
# the BENCH_engine.json schema the perf trajectory is built from ----------
python -m benchmarks.engine_throughput --smoke --depths 1,2 \
    --out "$smoke_dir/BENCH_engine.json" > /dev/null
python - "$smoke_dir" <<'PY'
import json, sys
doc = json.load(open(sys.argv[1] + "/BENCH_engine.json"))
assert doc["benchmark"] == "engine_throughput"
assert doc["schema_version"] >= 1    # v2 added env details + stall columns
assert doc["graphs"] and doc["results"]
assert all(g["edges"] > 0 and g["vertices"] > 0
           for g in doc["graphs"].values())
legacy = [r for r in doc["results"] if r["config"] == "legacy"]
piped = [r for r in doc["results"] if "speedup_vs_legacy" in r]
assert legacy and piped, "need both legacy baseline and pipelined rows"
for r in doc["results"]:
    assert r["seconds"] > 0 and r["edges_per_sec"] > 0
s = doc["summary"]
assert {"geomean_best_speedup", "per_algo_geomean_best_speedup",
        "target_speedup", "meets_target"} <= set(s)
print(f"bench smoke OK: geomean {s['geomean_best_speedup']}x over the "
      f"synchronous engine (tiny graph — schema check, not a perf gate)")
PY

# ---- quality-smoke stage: RF per registered spec on a tiny pinned graph,
# then validate the BENCH_engine.json quality-section schema -------------
python -m benchmarks.quality --smoke \
    --out "$smoke_dir/BENCH_engine.json" > /dev/null
python - "$smoke_dir" <<'PY'
import json, sys
from repro.core import SPEC_REGISTRY
doc = json.load(open(sys.argv[1] + "/BENCH_engine.json"))
assert "results" in doc, "quality merges into the engine doc, not over it"
q = doc["quality"]
assert q["schema_version"] >= 1
assert q["graphs"] and q["results"]
algos = {r["algorithm"] for r in q["results"]}
assert algos == set(SPEC_REGISTRY), \
    f"quality rows must cover the registry: {sorted(algos)}"
for r in q["results"]:
    assert r["replication_factor"] >= 1.0 and r["balance"] >= 1.0
s = q["summary"]
for g, ratio in s["buffered_vs_2psl_rf_ratio"].items():
    assert ratio <= 1.0, f"buffered lost to 2psl on {g}: {ratio}"
for g, h in s["hep_budget"].items():
    assert h["within_budget"], f"hep over budget on {g}: {h}"
print(f"quality smoke OK: {len(q['results'])} rows over "
      f"{len(algos)} specs; buffered/2psl ratios "
      f"{list(s['buffered_vs_2psl_rf_ratio'].values())}")
PY

# ---- serve-smoke stage: lower the artifact into per-partition serving
# structure (--local-graphs, artifact format v3), sample ego-networks, and
# answer GNN inference through serve_gnn with the hot-vertex cache — the
# JSON report must show latency percentiles and a nonzero cache hit-rate -
python -m repro.launch.partition \
    --input "$smoke_dir/graph.bin" --k 4 --algorithm 2psl \
    --chunk-size 256 --artifact-dir "$smoke_dir/artifact_serve" \
    --local-graphs --json > /dev/null
python -m repro.launch.serve --gnn-artifact "$smoke_dir/artifact_serve" \
    --requests 8 --roots-per 3 --json > "$smoke_dir/serve.json"
python - "$smoke_dir" <<'PY'
import json, sys
import numpy as np
from repro.core import PartitionArtifact
from repro.sample import PartitionedGraph, PartitionedNeighborSampler
art = PartitionArtifact.load(sys.argv[1] + "/artifact_serve")
assert art.manifest["format_version"] == 4 and art.has_local_graphs()
assert art.manifest["integrity"]["files"], "v4 artifact must be checksummed"
pg = PartitionedGraph.load(art)
out = PartitionedNeighborSampler(pg, (-1, -1)).sample(np.arange(4))
assert out["edge_mask"].sum() > 0
rep = json.loads(open(sys.argv[1] + "/serve.json").read()
                 .strip().splitlines()[-1])
assert rep["mode"] == "gnn" and rep["p99_ms"] >= rep["p50_ms"] > 0
assert rep["cache"]["hit_rate"] > 0, rep["cache"]
print(f"serve smoke OK: p50 {rep['p50_ms']}ms p99 {rep['p99_ms']}ms "
      f"cache hit-rate {rep['cache']['hit_rate']}")
PY

# ---- crash-resume smoke stage: hard-kill a checkpointed partition run
# after its 2nd checkpoint (REPRO_CRASH_AFTER_CHECKPOINTS -> os._exit, no
# atexit/flush), then --resume it — the recovered assignment must be
# byte-identical to an uninterrupted run and the manifest must record the
# resume (docs/robustness.md) --------------------------------------------
if REPRO_CRASH_AFTER_CHECKPOINTS=2 python -m repro.launch.partition \
    --input "$smoke_dir/graph.bin" --k 4 --algorithm 2psl \
    --chunk-size 128 --artifact-dir "$smoke_dir/artifact_crash" \
    --checkpoint-every 2 --no-plan --json > /dev/null
then echo "crash stage: run survived the kill"; exit 1; else rc=$?; fi
[[ "$rc" == 137 ]] || { echo "crash stage: expected exit 137, got $rc"; exit 1; }
[[ ! -f "$smoke_dir/artifact_crash/manifest.json" ]] \
    || { echo "crash stage: killed run left a manifest"; exit 1; }
python -m repro.launch.partition \
    --input "$smoke_dir/graph.bin" --k 4 --algorithm 2psl \
    --chunk-size 128 --artifact-dir "$smoke_dir/artifact_crash" \
    --checkpoint-every 2 --resume --no-plan --json \
    > "$smoke_dir/resume.json"
python - "$smoke_dir" <<'PY'
import hashlib, json, sys
rep = json.load(open(sys.argv[1] + "/resume.json"))
assert rep["resumes"] >= 1, rep
manifest = json.load(open(sys.argv[1] + "/artifact_crash/manifest.json"))
assert manifest["extras"]["resumes"] >= 1
sha = lambda p: hashlib.sha256(open(p, "rb").read()).hexdigest()
resumed = sha(sys.argv[1] + "/artifact_crash/assignment.bin")
clean = sha(sys.argv[1] + "/artifact/assignment.bin")
print(f"crash-resume smoke OK: resumed assignment sha256 {resumed[:12]}.. "
      f"(resumes={manifest['extras']['resumes']})")
PY
python - "$smoke_dir" <<'PY'
# byte-identity vs a clean run at the same spec/chunking
import hashlib, subprocess, sys, os
d = sys.argv[1]
subprocess.run(
    [sys.executable, "-m", "repro.launch.partition", "--input",
     d + "/graph.bin", "--k", "4", "--algorithm", "2psl", "--chunk-size",
     "128", "--artifact-dir", d + "/artifact_clean128", "--no-plan",
     "--json"], check=True, stdout=subprocess.DEVNULL)
sha = lambda p: hashlib.sha256(open(p, "rb").read()).hexdigest()
a = sha(d + "/artifact_crash/assignment.bin")
b = sha(d + "/artifact_clean128/assignment.bin")
assert a == b, f"resumed {a[:12]} != clean {b[:12]}"
print("crash-resume byte-identity OK")
PY

# ---- shard-smoke stage: 2-worker emulated sharded run on the pinned
# rmat13-s11 graph -> stitched format-v4 artifact; the load verifies the
# checksums, the manifest must carry the shards block with per-rank slice
# sha256s, and RF must land within 5% of the sequential engine at the
# same spec (docs/distributed.md) -----------------------------------------
python - "$smoke_dir" <<'PY'
import sys
import numpy as np
from repro.data import rmat_graph
g = rmat_graph(13, edge_factor=8, seed=11)
g.astype(np.uint32).tofile(sys.argv[1] + "/rmat.bin")
PY
python -m repro.launch.partition \
    --input "$smoke_dir/rmat.bin" --k 8 --algorithm 2psl \
    --chunk-size 1024 --artifact-dir "$smoke_dir/artifact_seq_rmat" \
    --no-plan --json > "$smoke_dir/seq_rmat.json"
python -m repro.launch.dist_partition \
    --input "$smoke_dir/rmat.bin" --k 8 --algorithm 2psl \
    --chunk-size 1024 --workers 2 --backend emulated \
    --artifact-dir "$smoke_dir/artifact_shard" \
    --no-plan --json > "$smoke_dir/shard.json"
python - "$smoke_dir" <<'PY'
import json, sys
from repro.core import PartitionArtifact
d = sys.argv[1]
art = PartitionArtifact.load(d + "/artifact_shard")   # checksum verify
sh = art.manifest["shards"]
assert sh["num_shards"] == 2 and len(sh["slices"]) == 2, sh
assert all(len(s["sha256"]) == 64 for s in sh["slices"])
seq = json.load(open(d + "/seq_rmat.json"))["replication_factor"]
rf = json.load(open(d + "/shard.json"))["replication_factor"]
assert abs(rf - seq) <= 0.05 * seq, (seq, rf)
print(f"shard smoke OK: 2-worker rf={rf:.3f} vs sequential {seq:.3f} "
      f"(rounds={sh['rounds']}, {len(sh['slices'])} checksummed slices)")
PY

# ---- docs stage: README.md + docs/*.md must exist and their '# doc-test'
# tagged fenced python blocks must execute (examples cannot rot) ----------
python scripts/doc_tests.py
echo "docs stage OK"

# ---- multihost stage (opt-in): host-grouped SPMD parity in subprocesses
# with 8 emulated host devices — minutes, so never part of the fast loop.
# --full already runs every slow test, so the stage would only duplicate
# work there ------------------------------------------------------------
if [[ "$multihost" == 1 && ${#marker[@]} -gt 0 ]]; then
    python -m pytest -x -q -m slow tests/test_partitioned_gnn.py \
        -k "egnn or hostgrouped"
    echo "multihost stage OK: host-grouped EGNN + GIN SPMD parity"
fi

# no exec: the EXIT trap must still fire to clean up the smoke dir
python -m pytest -x -q "${marker[@]}" "$@"
