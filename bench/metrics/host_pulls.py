"""host_pulls: ``HOST_SYNC_STATS.fused_pulls + count_pulls`` over the
window, per attempted pass."""


def read(ctx):
    load = ctx["load"]
    n = load.get("counters", {}).get("host_pulls")
    return None if n is None else n / load["attempted"]
