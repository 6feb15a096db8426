"""A kernel's share of its roofline, in %: the least time the chip could take
for the kernel's calls (``kernels/<work>.py``: max of FLOPs ÷ peak and bytes ÷
bandwidth, from the shapes in the configuration) ÷ the device time of the
kernel's events in the trace, found by the metric's ``match`` pattern on the
``XLA Ops`` line (``events_per_call`` of them make one call). No matching
event → nothing to read → no number."""
import loader
import trace_reduce


def read(ctx):
    spec = ctx['metric']
    kernel = loader.load_module('kernels', spec['kernel'])
    total_ns, calls = 0.0, 0
    planes = trace_reduce.device_planes(ctx['trace'])
    for plane in planes:
        ns, n = trace_reduce.sum_matching(
            trace_reduce.line_events(plane, trace_reduce.OPS_LINE),
            spec['match'])
        total_ns += ns
        calls += n
    if not calls or not total_ns:
        return None
    calls /= float(spec.get('events_per_call', 1))
    shape = kernel.shapes(ctx['config'], ctx['batch_size'])
    least, bound = kernel.min_seconds(ctx['peaks'], **shape)
    share = 100.0 * least * calls / (total_ns / 1e9)
    ctx['log'](f'{spec["name"]}: {calls:g} calls, {total_ns / 1e9:.3f} s on '
               f'the device, {least * 1e3:.3f} ms least a call, bound by '
               f'{bound}')
    return share
