"""compiles_in_window: executables JAX built or loaded inside the window
(``jax.monitoring`` backend compile events)."""


def read(ctx):
    return ctx["load"].get("counters", {}).get("compiles_in_window")
