"""ingest_ms: the benchmark's span around ``EngineKB.from_arrays`` (stores
ready), summed over the window's passes and divided by their number."""


def read(ctx):
    spans = ctx["load"].get("spans", {}).get("ingest")
    return 1e3 * sum(b - a for a, b in spans) / len(spans) if spans else None
