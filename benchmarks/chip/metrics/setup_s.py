"""setup_s: seconds from the start of the process to the start of the
window — JAX's start, the data, the index or warm-up builds, and every
compile or cache load.  Host clock."""


def read(run):
    return run.setup_s
