"""The benchmark's harness: cell files, traffic, weights, the wall-clock
serving window, the profiler-trace reduction and the output check.

Everything a cell needs is found by the names in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<mix>.json``, ``cells/<cell>.json``
and ``metrics/<metric>.py``, all under ``bench/``.
"""
