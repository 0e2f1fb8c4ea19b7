"""The chip benchmark of superlu_dist_tpu: one cell of BENCHMARK.json per
process (``python3 benchmark/run.py --workload <cell> ...``).

Everything that decides a number lives here, apart from the program under
test: matrix generators, traffic, the plain reference and the limits it is
held to, the trace reduction, the metric readers and the table of peaks.
"""
