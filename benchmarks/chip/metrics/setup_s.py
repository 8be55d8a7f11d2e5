"""Set-up: from process start to the window's opening (imports, weights,
engine, compiles or cache loads, warm-up)."""


def read(rec):
    return rec.setup_s
