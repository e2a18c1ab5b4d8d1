"""Device milliseconds of one decode token step spent attending over the
chosen tokens in the full latent layers: the operations of the two decode
programs whose scope path lies under ``dsa_attn`` (the ``mla_decode`` kernel over the
sequence's pages with the choice in its mask, and whatever lays the choice
out for it), over the decode
token steps of the traced window. The absorb products lie under
``mla_absorb`` and are not counted here. A program without the scope reads
nothing."""

from benchmarks.layer_metrics.dsa_index_ms import per_step_ms


def read(ctx, result):
    return per_step_ms(ctx, result, ("dsa_attn",))
