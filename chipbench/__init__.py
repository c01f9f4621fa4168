"""Chip benchmark for the QueryService path (``python3 chipbench/run.py``).

Everything a cell needs is found by name from ``BENCHMARK.json``:
``configs/<config>.json`` and ``configs/<config>.py`` (the deployment),
``traffic/<mix>.json`` (read by ``loadgen``, which finds the mix's arrival
law in ``loops/<loop>.py`` and its size law in ``sizes/<law>.py``),
``metrics/<metric>.py`` (one reader per metric), ``peaks.json`` (peaks by
``device_kind``).
"""
