"""setup_s: seconds from the process's start to the window's: imports,
reaching the chip, compiling or loading the compiled programs, and making
the inputs and weights (host clock)."""


def read(run):
    return run["setup_s"]
