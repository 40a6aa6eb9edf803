"""Model step: layers of the paged decode step that update the KV pool
in place (the scheduler's ``kv_inplace_layers`` counter, as the window
closes)."""


def read(run):
    return run.counters_close.get("kv_inplace_layers")
