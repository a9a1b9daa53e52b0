"""Engine apply: share of the window's assigned rows that took the
per-row same-server path, counter ``engine.fallback.same_server_conflict``
over ``engine.tasks.assigned``."""


def read(ctx):
    assigned = ctx.counters.get("engine.tasks.assigned", 0)
    if assigned <= 0:
        return None
    return 100.0 * ctx.counters.get("engine.fallback.same_server_conflict",
                                    0) / assigned
