"""Bytes of a Pallas kernel call, read from compiled program text, and the
table of peaks.

``fixtures/chunk_custom_calls.txt`` holds the ``tpu_custom_call`` lines
of a fused chunk compiled on a TPU v5e chip (``compiled_chunk_text()``
of a PageRank and an SSSP engine, kernel bodies elided): the row sweep's
call and the cold slate's call, batched over 8 rows.
"""
from pathlib import Path

import pytest

from bench import roofline

FIXTURE = Path(__file__).parent / "fixtures" / "chunk_custom_calls.txt"
ROW = 4 * (256 + 8 * 512 + 8 * 512 + 256)  # agg, messages, slots, result


def test_block_sweep_bytes_from_compiled_text():
    lines = FIXTURE.read_text().splitlines()
    got = [roofline.kernel_call_bytes(line, "block_sweep") for line in lines]
    assert got == [{"block_sweep.5": ROW}, {"block_sweep.4": 8 * ROW}] * 2
    assert roofline.kernel_call_bytes("\n".join(lines), "other") == {}


def test_trace_event_text_reads_the_same():
    # a TPU trace names the event by the same instruction, with its
    # operands' shapes inline; a narrower bucket batches fewer rows
    event = ("%block_sweep.4 = f32[4,1,256]{2,1,0:T(1,128)S(1)} custom-call("
             "f32[4,1,256]{2,1,0:T(1,128)S(1)} %get-tuple-element.2716, "
             "f32[4,8,1,512]{3,2,1,0:T(1,128)S(1)} %fusion.29, "
             "s32[4,8,1,512]{3,2,1,0:T(1,128)S(1)} %broadcast_in_dim.57), "
             'custom_call_target="tpu_custom_call", operand_layout_constraints'
             "={f32[4,1,256]{2,1,0}, f32[4,8,1,512]{3,2,1,0}, "
             "s32[4,8,1,512]{3,2,1,0}}, frontend_attributes={kernel_metadata"
             "={}}")
    assert roofline.kernel_call_bytes(event, "block_sweep") == {
        "block_sweep.4": 4 * ROW}


def test_shape_bytes():
    assert roofline.shape_bytes("f32[8,1,512]{2,1,0:T(1,128)}") == 16384
    assert roofline.shape_bytes("(s32[], pred[4], bf16[2,3])") == 4 + 4 + 12


def test_peaks_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
