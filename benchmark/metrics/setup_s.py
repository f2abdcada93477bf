"""Set-up seconds: process start to the window's start, building the
cell, compiling or loading its programs and warming its shapes."""


def read(run):
    return run.setup_s
