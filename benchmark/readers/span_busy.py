"""Share of the traced window in which a stage of the program was busy, in %:
seconds inside the span named by the metric's ``span`` ÷ window seconds, from
the program's stage table (``utils/tracing.Tracer``, folded into the run
manifest). Time busy, not time waited for: the span runs on the producer side
of the read-ahead. No such span recorded → no number."""


def read(ctx):
    span = ctx['stages'].get(ctx['metric']['span'], {})
    if not span.get('count'):
        return None
    return 100.0 * span['total_s'] / ctx['window_s']
