"""Share of mixed (prefill-carrying) steps that took the paged prefill
kernel and not the gather path: ``prefill_kernel_steps`` over itself plus
``prefill_gather_fallbacks``, window delta of the engine's counters."""


def read(ctx, result):
    c = result["counters"]["engine"]
    total = c["prefill_kernel_steps"] + c["prefill_gather_fallbacks"]
    if not total:
        return None
    return 100.0 * c["prefill_kernel_steps"] / total
