"""materialize_ms: the benchmark's span around ``materialize`` and the wait
for every store, summed over the window's passes and divided by their
number."""


def read(ctx):
    spans = ctx["load"].get("spans", {}).get("materialize")
    return 1e3 * sum(b - a for a, b in spans) / len(spans) if spans else None
