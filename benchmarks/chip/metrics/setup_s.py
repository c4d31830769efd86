"""Seconds from the start of the process to the start of the window:
imports, corpus, index build, compilation or compile-cache loads, and the
warm-up of the window's own shapes."""


def read(run):
    return run.setup_s
