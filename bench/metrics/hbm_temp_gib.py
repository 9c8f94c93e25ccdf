"""The compiled step's temporary buffers, as XLA's buffer assignment sizes
them (`memory_analysis().temp_size_in_bytes`), in GiB."""


def read(ctx):
    return ctx.hbm_temp_bytes / 2**30
