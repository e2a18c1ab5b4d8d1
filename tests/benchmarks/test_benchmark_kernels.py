"""Operations and bytes against hand arithmetic at the cells' shapes."""

import pytest

from benchmarks.harness import device, flops
from benchmarks.kernels import flash, paged_decode
from benchmarks.references import mistral
from benchmarks.references.mistral import Arch

MISTRAL = dict(hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
               head_dim=128, intermediate_size=14336, vocab_size=32000,
               rope_theta=1e4, rms_norm_eps=1e-5)


def test_flash_forward_at_the_training_shape():
    # micro 4, S 2048, 32 q / 8 kv heads, D 128, causal
    f, b = flash.fwd(4, 2048, 32, 8, 128)
    pairs = 2048 * 2049 // 2
    assert f == 4 * 128 * pairs * 32 * 4
    q = 4 * 2048 * 32 * 128 * 2
    kv = 2 * 4 * 2048 * 8 * 128 * 2
    assert b == 2 * q + kv + 4 * 2048 * 32 * 4
    assert f == pytest.approx(137.5e9, rel=1e-3)


def test_flash_backward_is_five_products_to_the_forwards_two():
    f, _ = flash.fwd(4, 2048, 32, 8, 128)
    fb, bb = flash.bwd(4, 2048, 32, 8, 128)
    assert fb == pytest.approx(2.5 * f)
    assert bb > flash.fwd(4, 2048, 32, 8, 128)[1]


def test_flash_noncausal_is_twice_the_pairs_less_the_diagonal():
    c, _ = flash.fwd(1, 1024, 32, 8, 128, causal=True)
    n, _ = flash.fwd(1, 1024, 32, 8, 128, causal=False)
    assert n / c == pytest.approx(2 * 1024 / 1025)


def test_flash_is_compute_bound_on_a_v5e():
    t, bound = flash.floor_seconds(*flash.fwd(4, 2048, 32, 8, 128),
                                   device.peaks("TPU v5 lite"))
    assert bound == "compute"
    assert t == pytest.approx(137.5e9 / 197e12, rel=1e-3)


def test_paged_decode_reads_each_context_token_once():
    ctx = [640, 300, 1]
    f, b = paged_decode.call(ctx + [0, 0], 32, 8, 128)
    tokens = 941
    assert f == 4 * 128 * 32 * tokens
    assert b == 2 * tokens * 8 * 128 * 2 + 2 * 3 * 32 * 128 * 2
    t, bound = flash.floor_seconds(f, b, device.peaks("TPU v5 lite"))
    assert bound == "memory"


def test_train_flops_per_token_at_two_layers():
    a = Arch(num_hidden_layers=2, **MISTRAL)
    per_layer = 4096 * 128 * (2 * 32 + 2 * 8) + 3 * 4096 * 14336
    assert mistral.matmul_params(a) == 2 * per_layer + 4096 * 32000
    fpt = mistral.train_flops_per_token(a, 2048)
    attn = 2 * 2.0 * 2048 * 128 * 32
    assert fpt == pytest.approx(3 * (2 * mistral.matmul_params(a) + attn))
    assert fpt == pytest.approx(3.5e9, rel=0.03)       # ISSUE 23: ~3.5 GFLOP
    assert flops.mfu(30600, fpt, 197e12) == pytest.approx(0.54, abs=0.02)


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(device.UnknownDeviceError):
        device.peaks("cpu")


def _kernel_event(name):
    return (f'%{name} = (bf16[128,2048,128]{{2,1,0:T(8,128)(2,1)}}, f32[128,2048,8]'
            '{2,1,0:T(8,128)}) custom-call(bf16[128,2048,128]{2,1,0} %bitcast.450, '
            'bf16[32,2048,128]{2,1,0} %bitcast.457, bf16[32,2048,128]{2,1,0} '
            '%bitcast.473), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={}')


@pytest.mark.parametrize("name,flash_class,decode_class", [
    ("flash_fwd.17", "fwd", None), ("flash_fwd", "fwd", None),
    ("flash_bwd_dkdv.3", "bwd", None), ("flash_bwd_dq.4", "bwd", None),
    ("paged_decode.6", None, "decode"), ("paged_prefill.2", None, None),
    ("grouped_matmul.9", None, None), ("delta_rule_chunk.1", None, None)])
def test_kernels_are_told_by_the_name_the_program_gives_them(
        name, flash_class, decode_class):
    """Whatever the operands: a program with three kernels in one decode
    step is read the same, and a kernel nobody asked about is nobody's."""
    ev = _kernel_event(name)
    assert flash.classify(ev) == flash_class
    assert paged_decode.classify(ev) == decode_class


def test_an_event_that_is_no_mosaic_kernel_has_no_kernel_name():
    from benchmarks.harness.trace import kernel_name

    assert kernel_name(_kernel_event("flash_fwd.17")) == "flash_fwd"
    assert kernel_name("%flash_fwd.2 = bf16[8]{0} fusion(%p), kind=kLoop") is None
    assert kernel_name('%paged_decode.1 = bf16[8]{0} custom-call(%p), '
                       'custom_call_target="Sharding"') is None
