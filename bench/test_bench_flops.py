"""Operation and byte counts against hand counts at a reduced size."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import flops  # noqa: E402

# 2 layers, width 8, 2 heads of 4, FFN 32, vocabulary 10
D = {"L": 2, "D": 8, "H": 2, "dh": 4, "F": 32, "V": 10}


def test_quant_matmul_call_by_hand():
    ops, nbytes = flops.quant_matmul_call(M=3, K=8, N=32)
    assert ops == 2 * 3 * 8 * 32                      # 1536
    # int8 weights 256 + f32 scales 128 + bf16 x 48 + bf16 out 192
    assert nbytes == 256 + 128 + 48 + 192


# operation texts as the v5e trace names them (layouts shortened)
PREFILL_FFN = (
    "%quant_matmul.46 = bf16[4096,10240]{1,0:T(8,128)(2,1)S(1)} "
    "custom-call(bf16[4096,2560]{1,0:T(8,128)(2,1)} %bitcast.130, "
    "s8[2560,10240]{1,0:T(8,128)(4,1)} %fusion.32, "
    "f32[1,10240]{1,0:T(1,128)S(1)} %fusion.33), "
    "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
    "{bf16[4096,2560]{1,0}, s8[2560,10240]{1,0}, f32[1,10240]{1,0}}")
DECODE_INT4 = (
    "%quant_matmul.3 = bf16[8,1,2560]{2,1,0} custom-call("
    "bf16[8,1,2560]{2,1,0} %x, s8[1280,2560]{1,0} %q, f32[1,2560]{1,0} "
    "%s), custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("op,want", [
    (PREFILL_FFN, (2 * 4096 * 2560 * 10240,
                   2560 * 10240 + 4 * 10240 + 2 * 4096 * 2560
                   + 2 * 4096 * 10240, False)),
    # packed int4: 1280 rows of bytes hold K = 2560 rows of weights;
    # the leading (8, 1) of x are its M = 8 rows
    (DECODE_INT4, (2 * 8 * 2560 * 2560,
                   2560 * 2560 / 2 + 4 * 2560 + 2 * 8 * 2560
                   + 2 * 8 * 2560, False)),
], ids=["prefill_w8a16", "decode_w4a16"])
def test_calls_priced_from_their_hlo(op, want):
    assert flops.quant_matmul_from_hlo(op) == want


def test_hlo_of_another_operation_is_refused():
    with pytest.raises(ValueError):
        flops.quant_matmul_from_hlo(
            "%fusion.3 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %a)")


def test_roofline_takes_the_larger_bound():
    assert flops.roofline_s(100.0, 10.0, 10.0, 10.0) == 10.0
    assert flops.roofline_s(10.0, 100.0, 10.0, 10.0) == 10.0


def test_token_flops_by_hand():
    weights = 4 * 8 * 8 + 2 * 8 * 32                  # 768 per layer
    # context 5: scores and values 2 * 2 * (2 * 4) * 5 = 160 per layer
    want = 2 * (2 * weights + 160) + 2 * 8 * 10
    assert flops.token_flops(D, 5) == want
    assert flops.token_flops(D, 5, logits=False) == want - 160


@pytest.mark.parametrize("s", [1, 3, 7])
def test_prefill_is_the_sum_of_its_tokens(s):
    want = sum(flops.token_flops(D, c + 1, logits=c == s - 1)
               for c in range(s))
    assert flops.prefill_flops(D, s) == pytest.approx(want)


@pytest.mark.parametrize("first,count", [(0, 1), (0, 5), (2, 3)])
def test_decode_counts_tokens_after_the_first(first, count):
    s = 4
    want = sum(flops.token_flops(D, s + i)
               for i in range(max(first, 1), first + count))
    assert flops.decode_flops(D, s, first, count) == pytest.approx(want)
