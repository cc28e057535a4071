"""The chip benchmark's harness: cells, traffic, weights, trace reduction,
counts, the plain reference, and the run that ties them together.

Everything a cell needs is found by name under ``benchmarks/chip``:
``configs/<config>.json``, ``traffic/<mix>.json``,
``limits/<cell>.json`` and ``metrics/<metric>.py``.
"""
