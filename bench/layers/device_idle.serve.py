"""The chip's idle share of the traced serving window, in %: 100 times
one less the busy time (the union of device operations) over the window.
Moves ``itl_p95_ms``."""


def read(run):
    if run["job"] != "serve":
        return None
    red = run["trace"]
    if not red.n_chips or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
