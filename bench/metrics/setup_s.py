"""Set-up: from the harness start to the window, warm-up, compiles and the
load's ramp included (host clock)."""


def read(r):
    return r.setup_s
