"""Host seconds spent lowering and compiling the state's initialisation and
the train step in set-up (from the compile cache after a cell's first
run)."""


def read(ctx):
    return ctx.compile_s
