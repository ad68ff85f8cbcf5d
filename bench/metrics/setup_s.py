"""Set-up time: process start to the window's start (host clock). Loading,
compiling or loading compiled programs, weights, warm-up and the load's ramp
all count here."""


def read(run):
    return run.setup_s
