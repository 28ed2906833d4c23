"""setup.compile_s: seconds of set-up spent tracing, lowering and
compiling (or reading the persistent cache), as the union of JAX's
compile-event spans from process start to the window's opening."""


def read(ctx):
    return ctx["compile_setup_s"]
