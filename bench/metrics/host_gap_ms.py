"""Host time between one step's wait ending and the next step's dispatch,
mean over the window (host clock): batch synthesis, fitting it to the
step's inputs and handing it to the device."""


def read(ctx):
    gaps = ctx.host_gaps_s
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
