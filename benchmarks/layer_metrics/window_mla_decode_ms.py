"""Device milliseconds of one decode token step spent in the windowed latent
layers' mixer: the operations of the two decode programs whose scope path
lies under ``wmla`` (the first norm, the projections and the latent's write
to the ring, the absorb products, ``wmla_attn``: the ``mla_decode`` kernel
over the ring's pages with a lower bound, the gate and the output
projection), over the decode token steps of the traced window. With
``dsa_index_ms``, ``dsa_attn_ms`` and ``moe_decode_ms`` it splits
``decode_step_ms``. A program without the scope reads nothing."""

from benchmarks.layer_metrics.gdn_decode_ms import per_token_step_ms


def read(ctx, result):
    return per_token_step_ms(ctx, result, "wmla")
