"""Operations and bytes of the program's kernels, from their shapes.

One module per kernel, named as the kernel's instruction in the compiled
program (``window_rows`` for ``%window_rows_pallas.N``).  Each gives
``INSTRUCTION`` (the instruction-name prefix that marks the kernel in a
device trace) and ``cost(operands, result) -> (flops, bytes)``.
"""
