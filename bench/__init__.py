"""The chip benchmark of this repository: harness, drivers, traffic,
per-layer metric readers and the plain references that decide ``correct``.
Entry point: ``python3 bench/run.py`` (see its docstring)."""
