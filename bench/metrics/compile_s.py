"""Host seconds of lowering and compiling the timed step, or of loading it
from the persistent compilation cache."""


def read(ctx):
    return ctx["rec"]["compile_s"]
