"""Share of the calls a request waited for its first token that carried
none of it: ``1 - first_token_own_calls / first_token_calls``, window
delta, in percent. What the other requests of a burst, and the sequences
in decode, put in front of a prompt; 0 for a lone prompt whose every call
is a chunk of its own."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    own = P.counter_ratio(result, "first_token_own_calls",
                          "first_token_calls")
    return None if own is None else 100.0 * (1.0 - own)
