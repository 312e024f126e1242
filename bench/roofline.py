"""Peaks of each chip, and the bytes a Pallas kernel call moves.

``PEAKS`` is keyed by ``device_kind`` as JAX reports it. A device that is
not in the table is an error, never a default.

The bytes of a kernel call are read from the compiled program text, so
the count follows the kernel when its operands change: a
``tpu_custom_call`` instruction whose kernel name matches reads each of
its operands once and writes its result once. The block sweep is bound
by memory bandwidth: the segmented sum it computes needs one add per
message (the one-hot product on the MXU is how the kernel does it, not
work the algorithm needs), so its least time is its bytes over the
chip's HBM bandwidth.
"""
from __future__ import annotations

import re

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM at 819 GB/s
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}

_DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "f64": 8,
                "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+"
                   r"custom-call\(")
_OPERANDS = "operand_layout_constraints={"


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       "them to bench/roofline.py with their source") from None


def shape_bytes(text: str) -> int:
    """Bytes of every array shape written in ``text``, e.g. ``f32[8,1,512]``
    (layouts such as ``{2,1,0}`` are ignored)."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        count = 1
        for d in filter(None, dims.split(",")):
            count *= int(d)
        total += count * _DTYPE_BYTES[dtype]
    return total


def kernel_call_bytes(hlo_text: str, kernel: str) -> dict[str, int]:
    """``{instruction name: bytes}`` for each ``tpu_custom_call`` of the
    named Pallas kernel in a compiled program's text: its operands, whose
    shapes the call lists as ``operand_layout_constraints``, read once,
    and its result written once. The compiler names the instruction
    after the kernel (``block_sweep.3``)."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _CALL.match(line)
        if m is None or m.group(1).split(".")[0] != kernel:
            continue
        out[m.group(1)] = shape_bytes(m.group(2)) + shape_bytes(
            _braced(line, line.index(_OPERANDS) + len(_OPERANDS)))
    return out


def _braced(text: str, start: int) -> str:
    """The text from ``start`` up to the brace that closes the one just
    before it."""
    depth = 1
    for i in range(start, len(text)):
        depth += {"{": 1, "}": -1}.get(text[i], 0)
        if depth == 0:
            return text[start:i]
    raise ValueError("unbalanced braces in a compiled instruction")
