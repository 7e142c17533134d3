"""Plain ``jax.numpy`` references, one module per kernel, found by the
``kernel`` key of a configuration file.

Each module gives ``make_inputs(key, x, y)`` (the run's data, drawn on the
device from the seed), ``reference(*inputs)`` (computed and returned in the
inputs' dtype; the control gives it bfloat16 inputs) and
``bytes_moved(x, y)`` (the least HBM traffic of one call, from the shapes
alone, whatever config runs).  They import nothing of the program.
"""
