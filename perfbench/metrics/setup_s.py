"""Process start to window open: imports, cluster or map stood up,
programs compiled or loaded, warm-up, preconditioning."""


def read(r):
    return r.setup_s
