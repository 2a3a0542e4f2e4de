"""Generators, orders and engine flags found by file name give the same
edge stream and the same command line as the fixed lists they replaced.

The digests and argument lists are pinned from the code before the
lookups: the sha256 of the edge set (``generate``) and of the stream
(``stream_order``) at the configuration's CPU size, and each cell's
partition CLI arguments.
"""
import hashlib
import os

import pytest

from bench import harness, registry
from bench.graphs import generate, stream_order

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RMAT = {
    3: ("ad2b0ad532ebc2d8c1afc66579cbff6c34557586d7d7c0bf5a281cf4b162cc40",
        "1ab89ecba03186b6becbbbd709fe5966848850b7973c0ae7aa9d93fdbfc6003f"),
    2**31 + 11: (
        "ef238c4ffdcc1cb670cbc61fbc505bf1e7e7a3bef0feae34b090b11525a41ecc",
        "9db580a7965c4e65882da509ff4576232a27ecd04b22d9d8d2e6c49d08e03513"),
}
# both cells stream the same configuration in the same order
STREAMS = {(name, seed): digests for seed, digests in RMAT.items()
           for name in ("rmat20-k256.2psl", "rmat20-k256.dbh")}
FIXED = ["--input", "G", "--k", "256", "--algorithm", "{}", "--artifact-dir",
         "A", "--no-plan", "--alpha", "1.05", "--chunk-size", "65536"]
ARGV = {
    "rmat20-k256.2psl": [a.format("2psl") for a in FIXED]
    + ["--cluster-passes", "1"],
    "rmat20-k256.dbh": [a.format("dbh") for a in FIXED],
}


def _sha(a) -> str:
    return hashlib.sha256(a.tobytes()).hexdigest()


@pytest.mark.parametrize("name,seed", sorted(STREAMS))
def test_edge_set_and_stream_are_the_pinned_ones(name, seed):
    cell = registry.cell(ROOT, name)
    config = {**cell.config, **cell.config["small"]}
    edges = generate(config, seed)
    stream = stream_order(edges, cell.traffic["order"], seed)
    assert (_sha(edges), _sha(stream)) == STREAMS[name, seed]


@pytest.mark.parametrize("name", sorted(ARGV))
def test_job_argv_is_the_pinned_one(name):
    cell = registry.cell(ROOT, name)
    argv = harness.job_argv(cell, "G", "A")
    assert argv == ARGV[name]
    flags = {a[2:] for a in argv if a.startswith("--")}
    assert flags - set(cell.traffic.get("cli", {})) == registry.FIXED_FLAGS
