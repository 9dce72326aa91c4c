"""Plain references of the benchmark's configurations.

Each module computes, from the configuration file and the weights the
harness made, what a served stream's accumulators and decision must be.
They import nothing of the program under test: the filter design, the
fixed-point format plan and the MP solves are written out here again from
the paper's definitions, so that a change to the program that alters what
it computes shows as a mismatch.
"""
