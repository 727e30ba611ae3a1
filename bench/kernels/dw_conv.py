"""Depthwise conv: x [N, P, Hq, Wq, C] (padded, split into stride
phases), w [kh, kw, C] -> y [N, Ho, Wo, C]."""

from bench.kernels import size


def matches(operands, result) -> bool:
    return (len(operands) == 2 and len(operands[0]) == 5
            and len(operands[1]) == 3 and len(result) == 4)


def flops(operands, result) -> int:
    kh, kw, c = operands[1]
    n, ho, wo, _ = result
    return 2 * n * ho * wo * kh * kw * c


def bytes_moved(operands, result, itemsize) -> int:
    return itemsize * (size(operands[0]) + size(operands[1]) + size(result))
