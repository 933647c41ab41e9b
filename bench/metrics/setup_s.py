"""setup_s: process start to the window's start (import, data generation,
warm passes, executables built or loaded)."""


def read(ctx):
    return ctx["setup_s"]
