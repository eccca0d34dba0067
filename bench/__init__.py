"""On-chip benchmark of the LLM-42 serving engine.

``bench/run.py`` runs one cell of ``BENCHMARK.json``: a model configuration
(``bench/configs/<name>.json``) under a traffic mix
(``bench/traffic/<name>.json``), with the per-layer readers of
``bench/metrics/<name>.py``.  Everything else here is the yardstick: the
traffic generator, the weight initializer, the trace reduction, the peak
table, the FLOP and byte functions, and the plain f32 references that
decide ``correct``.
"""
