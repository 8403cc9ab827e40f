"""Process start to the first timed request: imports, device start,
data made on the device, the program built and compiled (or loaded from
the compilation cache), the gallery prepared and the warm-up."""


def read(ctx):
    return ctx.setup_s
