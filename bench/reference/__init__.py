"""Plain references the benchmark compares the program's answers with.

They import nothing of the program and take nothing it made: the weights
come from ``bench.model.make_weights`` and the inputs from ``bench.traffic``.
"""
