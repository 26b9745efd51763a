"""Seconds from the start of the process to the opening of the window:
starting JAX, loading or compiling every program, drawing the weights,
configuring and warming the engine, and generating the traffic."""


def read(rec):
    return rec["setup_s"]
