"""KPU conv: x [N, P, Hq, Wq, Ci] (padded, split into stride phases),
w [kh, kw, Ci, Co] -> y [N, Ho, Wo, Co]."""

from bench.kernels import size


def matches(operands, result) -> bool:
    return (len(operands) == 2 and len(operands[0]) == 5
            and len(operands[1]) == 4 and len(result) == 4)


def flops(operands, result) -> int:
    kh, kw, ci, co = operands[1]
    n, ho, wo, _ = result
    return 2 * n * ho * wo * kh * kw * ci * co


def bytes_moved(operands, result, itemsize) -> int:
    return itemsize * (size(operands[0]) + size(operands[1]) + size(result))
