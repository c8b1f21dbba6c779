"""Plain references the benchmark compares the program's answers with.

They import nothing of the program and take nothing it made: the weights
come from the configuration's family (``bench/families/``) and the inputs
from ``bench.inputs``.
"""
