"""Decides ``correct``: the timed jobs' artifact against the plain reference.

Every number compared has a limit, and the run is correct when each is at
or under its limit (the harness adds ``compiles_in_window``, limit 0).
All but one of those here are exact (limit 0):

* ``reload_failures``: the artifact does not load with its sha256 checks;
* ``digest_mismatch``: the assignment file's sha256 is not the one its
  manifest records;
* ``jobs_differing``: window jobs whose recorded sha256 differs from the
  artifact the comparison reads (all jobs run the same partition);
* ``unassigned``: edges without a partition in ``[0, k)``;
* ``over_capacity``: edges in the largest partition beyond the hard cap
  ``ceil(alpha * |E| / k)`` (algorithms with a cap);
* ``rf_vs_report``, ``alpha_vs_report``: the replication factor and the
  balance that the CLI reported, against numpy's from the assignment file.

The last, ``edges_vs_reference``, is the share of edges whose partition
differs from the reference's; its limit is the cell's, set from readings
of the program and of the control (``bench/limits/<cell>.json``).
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

from . import reference

ASSIGNMENT = "assignment.bin"
#: algorithms that hold every partition under the hard capacity
CAPPED = {"2psl"}
MANIFEST = "manifest.json"


def sha256(path: str) -> str:
    """``sha256:<hex>`` of a file, the form artifact manifests record."""
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 22), b""):
            h.update(block)
    return "sha256:" + h.hexdigest()


def recorded_digest(art_dir: str) -> str | None:
    """The assignment's sha256 as the artifact's manifest records it."""
    with open(os.path.join(art_dir, MANIFEST)) as f:
        manifest = json.load(f)
    return manifest.get("integrity", {}).get("files", {}).get(ASSIGNMENT)


def reference_assignment(edges: np.ndarray, config: dict, traffic: dict,
                         score_dtype: str = "float32",
                         stats: dict | None = None) -> np.ndarray:
    k, alg = int(config["k"]), traffic["algorithm"]
    if alg == "2psl":
        return reference.twopsl(edges, k, float(config["alpha"]),
                                int(traffic["chunk"]),
                                int(traffic["micro_batch"]),
                                float(traffic["max_vol_factor"]),
                                score_dtype, stats)
    if alg == "dbh":
        return reference.dbh(edges, k)
    raise ValueError(f"no reference for algorithm {alg!r}")


def mismatch_share(asg: np.ndarray, ref: np.ndarray) -> float:
    return float(np.count_nonzero(asg != ref)) / max(len(ref), 1)


def judge(edges, config: dict, traffic: dict, limits: dict, art_dir: str,
          digests: list, report: dict, reload) -> tuple[dict, dict]:
    """``(checks, facts)``: each check ``{value, limit}``, and the
    numbers the metrics take from the comparison (RF, scored share)."""
    k = int(config["k"])
    E = len(edges)
    checks = {}

    def put(name, value, limit=0):
        checks[name] = {"value": value, "limit": limit}

    try:
        reload(art_dir)
        put("reload_failures", 0)
    except Exception:  # noqa: BLE001 — any failure to reload is the finding
        put("reload_failures", 1)
    path = os.path.join(art_dir, ASSIGNMENT)
    digest = sha256(path)
    put("digest_mismatch", int(digest != recorded_digest(art_dir)))
    put("jobs_differing", sum(d != digest for d in digests))
    asg = np.fromfile(path, dtype=np.int32)
    bad = int(len(asg) != E) or int(np.count_nonzero((asg < 0) | (asg >= k)))
    put("unassigned", bad)
    facts = {}
    if not bad:
        rf, sizes = reference.replication_factor(edges, asg, k)
        balance = float(sizes.max()) / (E / k)
        facts["replication_factor"] = rf
        if traffic["algorithm"] in CAPPED:
            cap = reference.capacity(E, k, float(config["alpha"]))
            put("over_capacity", max(int(sizes.max()) - cap, 0))
        put("rf_vs_report", abs(rf - float(report["replication_factor"])))
        put("alpha_vs_report", abs(balance - float(report["alpha_measured"])))
        ref = reference_assignment(edges, config, traffic, stats=facts)
        put("edges_vs_reference", mismatch_share(asg, ref),
            float(limits["edges_vs_reference"]["limit"]))
    return checks, facts
