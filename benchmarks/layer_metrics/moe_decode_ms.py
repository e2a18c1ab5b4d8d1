"""Device milliseconds of one decode token step spent in the expert
layers: the operations of the two decode programs whose scope path lies
under ``moe`` (routing, the three grouped products, the shared expert, the
combine), over the decode token steps of the traced window. With
``gdn_decode_ms`` it splits ``decode_step_ms``."""

from benchmarks.layer_metrics.gdn_decode_ms import per_token_step_ms


def read(ctx, result):
    return per_token_step_ms(ctx, result, "moe")
