"""Seconds from the start of the process to the first timed request: pool
up (the worker's TPU initialisation included), operands, the plan and one
full codeword of the cell's shape (compiles, or compile-cache loads)."""


def read(run):
    return run.setup_s
