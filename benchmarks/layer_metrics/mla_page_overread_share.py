"""How much more than its contexts the latent decode kernel fetched, because
it fetches whole pages: ``mla_pages_read`` x the page's tokens over
``mla_context_tokens`` of the engine (window delta; both summed over
sequences, layers and decode steps), minus one, in percent.
``mla_pages_read`` is counted inside the kernel, where it starts a page's
copy (``paged_attention._walk_pages``, ``started_ref``), so a walk that went
past a context or fetched a page twice reads higher here. Half a page a
sequence at most from whole pages alone: 0.1-0.4% at contexts of 8k-24k and
64-token pages."""



def read(ctx, result):
    c = result.get("counters", {}).get("engine", {})
    if not c.get("mla_context_tokens"):
        return None
    page = ctx.config["engine"]["kv_block_size"]
    return 100.0 * (c.get("mla_pages_read", 0) * page
                    / c["mla_context_tokens"] - 1.0)
