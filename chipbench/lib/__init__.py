"""The benchmark's own library.

``driver.py`` drives the program and ``cell.py`` runs one cell with it;
everything else (traffic, reference, peaks, bytes, trace reduction) is
plain numpy and imports nothing of the program.
"""
