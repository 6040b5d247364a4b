"""setup_s (end to end, host clock): seconds from the start of the
benchmark process to the start of the window — imports, operator
generation, ``make_solver`` set-up, the right-hand sides and the warm-up
solve, compilation or compile-cache loads included."""


def read(rec):
    return rec["setup_s"]
