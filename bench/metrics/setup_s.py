"""Set-up seconds: from the start of the process to the start of the
measured window (imports, inputs, index build, weights, compiles or
compile-cache loads, warm-up)."""


def read(r):
    return r.setup_s
