"""Squeeze-and-excitation gate: x [N, H, W, C] times its frame's gate
g [N, 1, C] -> y [N, H, W, C], one multiply per feature.

Not one of ``KINDS``: its calls keep kind ``mosaic`` in
``mosaic_calls`` and are told by their name, ``se_scale.<node>``
(``bench/metrics/se_scale_roofline.py``).  No other kind's ``matches``
accepts its operands: the first is 4-d, the second 3-d.
"""

from bench.kernels import size


def matches(operands, result) -> bool:
    return (len(operands) == 2 and len(operands[0]) == 4
            and len(operands[1]) == 3 and tuple(operands[0]) == tuple(result)
            and tuple(operands[1]) == (result[0], 1, result[3]))


def flops(operands, result) -> int:
    return size(result)


def bytes_moved(operands, result, itemsize) -> int:
    return itemsize * (size(operands[0]) + size(operands[1]) + size(result))
