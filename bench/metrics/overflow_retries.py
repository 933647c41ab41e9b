"""overflow_retries: ``HOST_SYNC_STATS.fused_retries`` (planner capacity
retries) over the window, per attempted pass."""


def read(ctx):
    load = ctx["load"]
    n = load.get("counters", {}).get("overflow_retries")
    return None if n is None else n / load["attempted"]
