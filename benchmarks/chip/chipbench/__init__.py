"""The chip benchmark's yardstick: cells, traffic, weights, driving,
accounting, trace reduction and the correctness check.

Everything here belongs to the benchmark, not to the program under test.
The program (``repro``, under ``src/``) is imported only for the system
itself: ``repro.api.LLM`` and the engine it builds.
"""
