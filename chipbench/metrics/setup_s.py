"""Set-up time: from the start of the process to the start of the
measured window — imports, building or loading, compiling or loading
compiled programs, and warming up."""


def read(run):
    return run.setup_s
