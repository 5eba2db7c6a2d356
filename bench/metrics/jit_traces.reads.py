"""Executor / compile: jit traces of the program's kernel cores inside
the window (``kernel.jit_traces`` delta).  Every shape is warmed up, so
it should read 0."""


def read(c):
    key = "kernel.jit_traces"
    return float(c.obs1.get(key, 0) - c.obs0.get(key, 0))
