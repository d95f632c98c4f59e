"""Parity cells for the tests and `chip_smoke.py` (`harness.py`). The
benchmark is `benchmark/run.py`; nothing returned here is quoted as a speed.
"""
