"""Process start to the start of the window, compilation included."""


def read(ctx):
    return ctx["setup_s"]
