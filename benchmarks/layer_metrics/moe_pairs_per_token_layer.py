"""(token, expert) pairs routed to the experts held here, per token and
expert layer, over the run's timed steps (the traced ones and the window's):
the step counters ``moe_local_pairs`` over ``moe_token_layers``
(``runtime/engine.py`` carries the model's counts into each step's row in
the hub). A share of ``held / outputs`` of a router that takes ``top_k``
expects ``top_k * held / outputs``: 1.0 at 8 x 16 / 128. It describes the
traffic the experts got, so that a window whose router drifted from the
listed load shows: the manifest wants a direction and neither is better. The
note gives the smallest and largest step and the first and last, the same
for the share of the held experts that got a row and for the fullest held
expert's rows over the mean, and the pairs beyond the row buffer (0: a step
that drops one stops the engine)."""

from benchmarks.harness.train_step import counted, counted_steps


def read(ctx, result):
    got = counted(result)
    if got is None:
        return None
    c, steps = got
    a = result["facts"]["arch"]
    held_layers = a.num_experts * a.expert_layers

    def over_steps(of):
        got = [of(r) for r in counted_steps(result)]
        return {"smallest": min(got), "largest": max(got), "first": got[0],
                "last": got[-1]}

    ctx.note({"moe_pairs_per_token_layer": {
        "steps": steps, "per_step": c,
        "by_step": over_steps(
            lambda r: r["moe_local_pairs"] / r["moe_token_layers"]),
        "experts_hit_share": over_steps(
            lambda r: r.get("moe_experts_hit", 0) / held_layers),
        "fullest_over_mean": over_steps(
            lambda r: r.get("moe_max_expert_rows", 0) * a.num_experts
            / max(r["moe_local_pairs"], 1)),
        "dropped_pairs": c.get("moe_dropped_pairs", 0.0)}})
    return c["moe_local_pairs"] / c["moe_token_layers"]
