"""On-chip benchmark of the partitioner: whole partition jobs, one per cell.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything the
benchmark needs to judge the program lives here: the graph generators, the
plain reference, the trace reduction, the work counts behind each roofline
share and the table of peaks.  From the program it takes only the
partition CLI, the artifact it writes, its stage timings and the names of
its jitted bodies.
"""
