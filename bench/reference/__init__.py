"""Plain references for the benchmark's comparisons.

They import nothing of the program: the rules are written out here from
the paper's equations and the configuration files, in NumPy (and SciPy
for the Beta quantile), in float64.  The same code run in a narrower
type is each cell's low-precision control.
"""
