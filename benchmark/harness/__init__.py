"""The benchmark's harness: general code that reads what each cell
needs from the data files named in ``BENCHMARK.json``."""
