"""Device milliseconds of one decode token step spent in latent attention:
the operations of the two decode programs whose scope path lies under ``mla``
(the first norm, ``mla_project``: down- and up-projections, norms, rotary and
the latent's write; ``mla_absorb``; ``mla_attn``: the ``mla_decode`` kernel;
the output projection), over the decode token steps of the traced window.
With ``moe_decode_ms`` it splits ``decode_step_ms``; a program without the
scope (any model without latent attention) reads nothing."""

from benchmarks.layer_metrics.gdn_decode_ms import per_token_step_ms


def read(ctx, result):
    return per_token_step_ms(ctx, result, "mla")
