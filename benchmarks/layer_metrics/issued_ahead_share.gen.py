"""Share of the window's decode bursts that were issued while the burst
before them was still unread: ``100 * calls_issued_ahead /
calls_multi_decode``, window delta of the engine's counters. 100 where
every burst runs behind another (a full batch in steady decode); under it
by the bursts the host waited for, which is the device's idle time in a
closed loop. Prints, as a note, why: every ``burst_refused_<reason>`` and
``ahead_refused_<reason>`` of ``_plan_decode_burst`` over the window,
beside ``bursts_planned`` and ``burst_steps_clamped`` (an engine that does
not count them prints no note)."""

from benchmarks.harness import program_calls as C

REASONS = ("burst_refused_", "ahead_refused_")
PLANNED = ("bursts_planned", "burst_steps_clamped")


def read(ctx, result):
    c = result.get("counters", {}).get("engine", {})
    why = {k: v for k, v in c.items()
           if k.startswith(REASONS) or k in PLANNED}
    if why:
        ctx.note({"burst_plans": why})
    return C.ratio(result, ["calls_issued_ahead"], ["calls_multi_decode"],
                   100.0)
