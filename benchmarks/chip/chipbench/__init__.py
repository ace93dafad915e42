"""The chip benchmark's yardstick: traffic generation, the plain reference,
trace reduction, byte counts and peaks.

Everything here is found by name from ``BENCHMARK.json``: a configuration is
``configs/<config>.json``, a traffic mix is ``traffic/<traffic>.json``, a
per-layer metric is ``metrics/<metric>.py`` and the correctness limits of a
cell are ``limits/<config>.<traffic>.json``, all under ``benchmarks/chip``.
"""
