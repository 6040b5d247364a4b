"""solve_ms (end to end, host clock): the window's length over the
solves completed in it, in milliseconds — one caller, closed loop, each
solve ended by ``block_until_ready``."""


def read(rec):
    return 1e3 * rec["window_s"] / len(rec["solve_s"])
