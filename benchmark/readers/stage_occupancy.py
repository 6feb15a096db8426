"""A counter of the program's stage table as a share, in %: ``occ_valid`` ÷
``occ_capacity`` of the stage named by the metric's ``stage``, over the
traced window (``utils/tracing.Tracer.add_occupancy``, folded into the run
manifest). The stages named under ``also_log`` are printed on stderr the same
way and reported nowhere. A program that records no such counter (a parent
commit, another family) → no number."""


def _share(stages, name):
    rec = stages.get(name, {})
    if not rec.get('occ_capacity'):
        return None
    return 100.0 * rec['occ_valid'] / rec['occ_capacity']


def read(ctx):
    for name in ctx['metric'].get('also_log', []):
        share = _share(ctx['stages'], name)
        if share is not None:
            rec = ctx['stages'][name]
            ctx['log'](f'counter {name}: {rec["occ_valid"]} / '
                       f'{rec["occ_capacity"]} = {share:.3f} %')
    return _share(ctx['stages'], ctx['metric']['stage'])
