"""Seconds from the process's start to the window's: loading, compiling or
reading the compile cache, prefilling the sessions, the first turns."""


def read(run):
    return run.setup_s
