"""Share of the traced window in which no operation ran on the device, in
percent: 1 - (union of device-op intervals) / window.  Should move
``output_tok_per_s``.
"""


def reduce(run):
    if run.trace is None:
        return None
    busy, window = run.trace.busy_window()
    if not window or not busy:
        return None
    return 100.0 * (1.0 - busy / window)
