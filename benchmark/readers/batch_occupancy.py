"""Valid ÷ capacity batch slots over the traced window, in %.

Where the program's stage table records slot counts on the ``model`` stage
(the packed scheduler does) those are read; the per-video loop records none,
so there the share is computed from the rows each video saved and the
driver's count of the slots those videos cost (``drivers/<d>.batch_slots``)."""


def read(ctx):
    model = ctx['stages'].get('model', {})
    if model.get('occ_capacity'):
        return 100.0 * model['occ_valid'] / model['occ_capacity']
    if ctx['slots']:
        return 100.0 * ctx['units'] / ctx['slots']
    return None
