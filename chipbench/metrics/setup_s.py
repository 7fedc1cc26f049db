"""From the start of the process to the start of the window: imports,
device check, building the cell, compiling and warming, the first steps."""


def read(ctx):
    return ctx.setup_s
