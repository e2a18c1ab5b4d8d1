"""Device milliseconds of one decode token step spent in the lightning
layers: the operations of ``jit_dstpu_serve_decode`` and
``jit_dstpu_serve_multi_decode`` whose scope path lies under ``lightning``
(norm, projections, QK-norm and rotary, the ``lightning_decode`` kernel,
output norm, gate and projection), over the decode token steps of the traced
window. With ``sparse_attn_decode_ms`` it splits the mixers' part of
``decode_step_ms``; a program without the scope reads nothing."""

from benchmarks.layer_metrics.gdn_decode_ms import per_token_step_ms


def read(ctx, result):
    return per_token_step_ms(ctx, result, "lightning")
