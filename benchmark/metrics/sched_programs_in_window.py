"""Programs compiled or loaded inside the window: the larger of JAX's
own count and the growth of the scheduler's program counts. Expected 0."""


def read(ctx):
    return ctx.get("programs_in_window")
