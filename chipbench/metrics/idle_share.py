"""Share (%) of the traced span in which no operation ran on the device,
averaged over the cell's chips."""


def read(ctx):
    return ctx.idle_share()
