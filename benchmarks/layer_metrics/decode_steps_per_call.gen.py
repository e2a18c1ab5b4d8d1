"""Token steps a call of the decode programs makes: ``(token_steps_decode
+ token_steps_multi_decode) / (calls_decode + calls_multi_decode)``, window
delta of the engine's per-program counters. ``decode_steps`` (8) where the
window holds whole bursts and nothing else; under it by the lone token
steps beside them and by the bursts cut to the shortest answer left. A
call pays the host's round trip once, however many steps it makes."""

from benchmarks.harness import program_calls as C

DECODE_PROGRAMS = ("decode", "multi_decode")


def read(ctx, result):
    return C.ratio(result, C.per_program("token_steps", DECODE_PROGRAMS),
                   C.per_program("calls", DECODE_PROGRAMS))
