"""facts_per_s: every fact in the final stores of every completed pass,
over the window from the first pass's start to the last pass's end."""


def read(ctx):
    t0, t1 = ctx["load"]["window"]
    return sum(ctx["load"]["facts"]) / (t1 - t0)
