"""peak_hbm_bytes: the device allocator's ``peak_bytes_in_use`` after the
window, on the fullest chip."""


def read(ctx):
    return ctx["peak_hbm_bytes"]
