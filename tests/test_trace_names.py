"""The benchmark's trace reader finds the paged decode kernels under the
names a TPU v5e trace gives them.

``bench/harness/trace.py`` credits a custom call to ``paged_decode`` when
its first operand is the (lanes, slots) int32 block table and its result
the (lanes, heads, head_dim) bfloat16 output; ``paged_decode_roofline``
reads that time. The operation below is copied from the ``XLA Ops`` line
of a profiler trace of ``paged_decode_pallas`` run on a TPU v5e chip (32
lanes, a 256-slot table, the Qwen3-30B-A3B pool).
"""
import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import trace  # noqa: E402

DECODE_ON_CHIP = (
    "%paged_decode.1 = bf16[32,32,128]{2,1,0:T(8,128)(2,1)S(1)} "
    "custom-call(s32[32,256]{1,0:T(8,128)} %bt.1, "
    "s32[32]{0:T(128)S(1)} %get-tuple-element.1, "
    "s32[33]{0:T(128)S(1)} %pad_add_fusion, "
    "bf16[32,32,128]{2,1,0:T(8,128)(2,1)} %q.1, "
    "bf16[4097,16,4,128]{3,2,1,0:T(4,128)(2,1)} %kp.1, "
    "bf16[4097,16,4,128]{3,2,1,0:T(4,128)(2,1)} %vp.1), "
    "custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={s32[32,256]{1,0}, s32[32]{0}, s32[33]{0}, "
    "bf16[32,32,128]{2,1,0}, bf16[4097,16,4,128]{3,2,1,0}, "
    "bf16[4097,16,4,128]{3,2,1,0}}, "
    "frontend_attributes={kernel_metadata={}}")


def test_kernel_of_finds_the_paged_decode_kernel():
    assert trace.kernel_of(DECODE_ON_CHIP) == "paged_decode"


# The latent decode kernel as compiled for a v5e chip (the long-document
# cell's call: 16 lanes, a 1040-slot table, rows of 576 values padded to
# 640), written as a trace names an operation: each operand's shape before
# its name. Its first operand is ctx_lens, a vector.
MLA_DECODE_ON_CHIP = (
    "%mla_decode.1 = bf16[16,32,512]{2,1,0:T(8,128)(2,1)} "
    "custom-call(s32[16]{0:T(128)} %get-tuple-element.1, "
    "s32[16,1040]{1,0:T(8,128)} %bt.1, "
    "s32[17]{0:T(128)} %pad_add_fusion, "
    "bf16[16,32,640]{2,1,0:T(8,128)(2,1)} %q.1, "
    "bf16[10241,16,640]{2,1,0:T(8,128)(2,1)} %p.1), "
    "custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={s32[16]{0}, s32[16,1040]{1,0}, s32[17]{0}, "
    "bf16[16,32,640]{2,1,0}, bf16[10241,16,640]{2,1,0}}, "
    "frontend_attributes={kernel_metadata={}}")


def test_kernel_of_finds_the_latent_decode_kernel_by_its_name():
    assert trace.kernel_of(MLA_DECODE_ON_CHIP) == "mla_decode"
    # with the block table in first place the reader would take the same
    # call for the GQA kernel: the operand order is what keeps them apart
    swapped = MLA_DECODE_ON_CHIP.replace(
        "custom-call(s32[16]{0:T(128)} %get-tuple-element.1, "
        "s32[16,1040]{1,0:T(8,128)} %bt.1, ",
        "custom-call(s32[16,1040]{1,0:T(8,128)} %bt.1, "
        "s32[16]{0:T(128)} %get-tuple-element.1, ")
    assert swapped != MLA_DECODE_ON_CHIP
    assert trace.kernel_of(swapped) == "paged_decode"
