"""Set-up: from the process's start to the window's start (imports, the
chip, the replicas' boot and the warm-up; in a traced run the kernel's
compile too)."""


def read(run):
    return run.setup_s
