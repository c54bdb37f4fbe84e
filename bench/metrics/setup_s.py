"""Seconds from the start of the process to the opening of the window:
loading, compiling or reading compiled programs, drawing the instances,
and the warm-up cuts."""


def read(run):
    return run.setup_s
