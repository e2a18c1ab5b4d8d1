"""Program calls a request waited for its first token: ``first_token_calls
/ first_tokens``, window delta: the calls of any program the engine issued
from the request's ``put`` to its first token, the one that produced it
included. The rung of the ladder ``ttft_p50_ms`` stands on (a first token
behind so many calls of 14 ms), counted and not timed."""

from benchmarks.harness import program_trace as P


def read(ctx, result):
    return P.counter_ratio(result, "first_token_calls", "first_tokens")
