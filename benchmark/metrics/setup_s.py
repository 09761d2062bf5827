"""setup_s: process start to window open — building the cluster and the
pod objects, loading or compiling and running the warm-up programs, and
creating the backlog. Host clock."""


def read(r):
    return r.setup_s
