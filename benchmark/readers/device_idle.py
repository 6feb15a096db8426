"""1 − (union of the device-op intervals ÷ traced window), in %."""


def read(ctx):
    reduced = ctx['reduced']
    if not reduced['busy_s']:
        return None
    return 100.0 * (1.0 - reduced['busy_s'] / reduced['window_s'])
