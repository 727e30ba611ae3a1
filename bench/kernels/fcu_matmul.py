"""FCU matmul: x [m, k] @ w [k, n] -> y [m, n].  Pointwise convs, the
classifier and the im2col stems all run it."""

from bench.kernels import size


def matches(operands, result) -> bool:
    return (len(operands) == 2 and len(operands[0]) == 2
            and len(operands[1]) == 2 and len(result) == 2
            and operands[0][1] == operands[1][0])


def flops(operands, result) -> int:
    (m, k), (_, n) = operands
    return 2 * m * k * n


def bytes_moved(operands, result, itemsize) -> int:
    return itemsize * (size(operands[0]) + size(operands[1]) + size(result))
