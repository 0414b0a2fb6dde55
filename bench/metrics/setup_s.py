"""Set-up: process start to the window's opening, in s (host clock):
loading, weights, compiling or loading from the cache, warm-up and warm-in."""
def read(run):
    return run.setup_s
