"""The benchmark: a data-driven harness around the checker's main path.
See run.py; BENCHMARK.json at the checkout's root names the cells."""
