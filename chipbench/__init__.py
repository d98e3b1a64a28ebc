"""Chip benchmark of `repro.core.sampler_api.run()`; see `BENCHMARK.json`
and `chipbench/run.py`."""
