"""The chip benchmark: one command (run.py) driven by BENCHMARK.json and the
data files beside it. Nothing here is imported by the program under test."""
