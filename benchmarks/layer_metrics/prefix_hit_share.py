"""Share of the admitted prompt tokens that the prefix cache served:
``prefix_hit_tokens`` over itself plus the scheduler's ``prefill_tokens``
(the tokens that still had to be prefilled), window delta."""


def read(ctx, result):
    hit = result["counters"]["engine"]["prefix_hit_tokens"]
    total = hit + result["counters"]["scheduler"]["prefill_tokens"]
    if not total:
        return None
    return 100.0 * hit / total
