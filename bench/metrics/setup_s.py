"""setup_s: seconds from process start to the window's start: imports,
device check, weights, engine, warm-up and any compilation."""


def read(r):
    return r.setup_s
