"""Process start to the first instant of the window."""
SOURCE = "host_clock"


def read(ctx):
    return ctx["setup_s"]
