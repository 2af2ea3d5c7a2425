"""Share of the dense (token, layer) KV entries the paged store did not
have to store, over the window: the engine's measured counters."""


def read(ctx):
    dense = ctx.counters.get("kv_entries_dense_measured_total", 0.0)
    if not dense:
        return None
    stored = ctx.counters.get("kv_entries_stored_measured_total", 0.0)
    return 100.0 * (1.0 - stored / dense)
