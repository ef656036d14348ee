"""The on-chip serving benchmark: ``BENCHMARK.json`` at the repository root
names its cells; ``run.py`` runs one (see its docstring)."""
