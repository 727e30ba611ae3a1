"""Operations and bytes of each kernel kind, and which compiled call is
which.  One module per kind (``bench/kernels/<kind>.py``), each with:

* ``matches(operands, result)``: whether a Mosaic custom call with these
  operand and result shapes is this kernel;
* ``flops(operands, result)``: the operations the call needs;
* ``bytes_moved(operands, result, itemsize)``: the bytes it must read and
  write at least (each operand and the result once).

Mosaic kernels carry no name of their own in the compiled program, so a
call is told by its shapes: the wrapper passes each kernel a fixed
operand layout (``src/repro/kernels/*/``).
"""

from __future__ import annotations

import dataclasses
import importlib
import re

KINDS = ("kpu_conv", "dw_conv", "fcu_matmul")
ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "s32": 4}

_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) custom-call\(")


def load(kind: str):
    return importlib.import_module(f"bench.kernels.{kind}")


@dataclasses.dataclass(frozen=True)
class Call:
    """One Mosaic call of a compiled program."""

    op: str          # HLO instruction name, as device trace events name it
    kind: str        # one of KINDS, or "mosaic" when no kind matches
    operands: tuple  # shapes
    result: tuple
    flops: int
    bytes: int


def _shapes(text: str):
    out = []
    for dtype, dims in _SHAPE.findall(text):
        out.append((dtype, tuple(int(d) for d in dims.split(",") if d)))
    return out


def mosaic_calls(hlo_text: str) -> dict:
    """{instruction name: Call} for every ``tpu_custom_call`` in a
    compiled program's HLO text."""
    calls = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _CALL.match(line)
        cons = re.search(r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=", line)
        if m is None or cons is None:
            continue
        res = _shapes(m.group(2))
        ops = _shapes(cons.group(1))
        if len(res) != 1 or not ops:
            continue
        dtype, result = res[0]
        operands = tuple(s for _, s in ops)
        item = ITEMSIZE.get(dtype, 4)
        kind, flops, nbytes = "mosaic", 0, 0
        for k in KINDS:
            mod = load(k)
            if mod.matches(operands, result):
                kind = k
                flops = mod.flops(operands, result)
                nbytes = mod.bytes_moved(operands, result, item)
                break
        calls[m.group(1)] = Call(m.group(1), kind, operands, result, flops, nbytes)
    return calls


def size(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n
