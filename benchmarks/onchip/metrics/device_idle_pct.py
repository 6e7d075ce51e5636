"""device_idle_pct.train (%), beside ``train_tokens_per_s``: the share of
the traced window in which no operation ran on the device (1 - union of
device-op intervals / window), averaged over the cell's chips."""


def read(ctx):
    red = ctx["trace"]
    return 100.0 * (1.0 - red.busy_s / red.window_s)
