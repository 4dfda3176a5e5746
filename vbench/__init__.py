"""The chip benchmark of Vedalia (see `BENCHMARK.json` and `vbench/run.py`)."""
