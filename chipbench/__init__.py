"""The chip benchmark of the sort service: ``python3 chipbench/run.py``.

Everything that decides a number lives here and nowhere in the program:
traffic generation, the plain reference that decides ``correct``, the peak
table, the byte functions, and the reduction from traces to metrics.
"""
