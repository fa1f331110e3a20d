"""perfbench: the repo's benchmark (BENCHMARK.json at the root names it).

Everything that decides a number lives here, where later PRs may add
files but not edit one: traffic generation, the reduction from traces
and spans to metrics, the table of peaks, FLOP counts, the plain
reference and the comparison that decides ``correct``.  From the program
it takes only the system under test.  See README.md.
"""
