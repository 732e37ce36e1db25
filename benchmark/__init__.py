"""The benchmark of ytk-mp4j-tpu: one cell a run, driven by the data files
named in ``BENCHMARK.json`` (see ``run.py`` and ``PERF.md``)."""
