"""The whole device step's share of the chip's bf16 peak, in %.

Model FLOPs of the valid work of the traced window (the configuration's
``flops_per_unit`` — counted on the benchmark's plain reference, so it reads
the same work whatever the program does — × the units saved; padded slots are
not work) ÷ (device time of the programs that ran in the window × peak).
Device time is the sum of the ``XLA Modules`` events of the trace, i.e. of
every program the device ran, the step being all but a sliver of it."""


def read(ctx):
    reduced = ctx['reduced']
    seconds = reduced['modules_total_s'] or reduced['busy_s']
    if not seconds or not ctx['units']:
        return None
    flops = ctx['config']['flops_per_unit'] * ctx['units']
    return 100.0 * flops / (seconds * ctx['peaks']['bf16_flops_per_s'])
