"""The main path's Mosaic kernels compiled at the benchmark cell's widths
for a DESCRIBED TPU v5e — no chip, nothing runs: the TPU compiler is
installed here and refuses what the interpreter accepts (a slice off the
tiling, more VMEM than a kernel may use). A pass is no chip run.

The topology is described inside a fixture, never at import (one process
at a time may load the TPU's library; a worker that cannot gets a skip),
and every such test lives in this one file so one worker loads it."""
import os

import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:      # no libtpu, or another process holds it
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize('passes', [3, 1])
def test_causal_attention_compiles_at_the_cells_widths(one_chip, passes):
    """One window of joyai-flash.corpus: 8,192 positions, 32 heads, q/k as
    (128 nope, 64 rope) groups with ONE rotary key, 128-wide v — under three
    passes (precision=mixed) and one (the control lane), at the shipped
    tiles and VMEM limit."""
    from video_features_tpu.ops.pallas_attention import causal_attention

    def sds(heads, width):
        return jax.ShapeDtypeStruct((1, 8192, heads, width), jnp.float32,
                                    sharding=one_chip)

    def attend(q_nope, q_rope, k_nope, k_rope, v):
        return causal_attention((q_nope, q_rope), (k_nope, k_rope), v,
                                192 ** -0.5, passes)

    compiled = jax.jit(attend).lower(
        sds(32, 128), sds(32, 64), sds(32, 128), sds(1, 64),
        sds(32, 128)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert '%causal_attention' in text


@pytest.mark.parametrize('passes', [3, 1])
def test_causal_attention_grouped_lane_compiles_at_the_cells_widths(one_chip,
                                                                    passes):
    """One window of lfm2-moe.corpus: 8,192 positions, 32 query heads
    reading 8 key-value heads, all 64 wide — a grid step one key-value head
    and its four query heads, 256 output columns — under three passes
    (precision=mixed) and one (the control lane), at the shipped tiles and
    VMEM limit; and that is what these shapes get on a TPU."""
    from video_features_tpu.ops.attention import KERNEL_PASSES, resolve_causal
    from video_features_tpu.ops.pallas_attention import causal_attention
    precision = {3: 'high', 1: 'default'}[passes]
    assert KERNEL_PASSES[precision] == passes
    assert resolve_causal('tpu', 8192, 64, 64, precision, 32, 8) == 'kernel'

    def sds(heads):
        return jax.ShapeDtypeStruct((1, 8192, heads, 64), jnp.float32,
                                    sharding=one_chip)

    compiled = jax.jit(lambda q, k, v: causal_attention(
        q, k, v, 64 ** -0.5, passes)).lower(sds(32), sds(8), sds(8)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert '%causal_attention' in text
    # q enters and the output leaves as they stand: no copy of either
    assert 'f32[1,32,8192,64]' not in text


@pytest.mark.parametrize('window,name,ring', [
    (2048, 'window_attention', 5),      # the six sliding layers
    (None, 'causal_attention', 32),     # the two full ones: 41.9 MB resident
])
@pytest.mark.parametrize('passes', [3, 1])
def test_causal_attention_compiles_at_trinity_minis_widths(one_chip, passes,
                                                           window, name,
                                                           ring):
    """One window of trinity-mini.corpus: 32,768 positions, 32 query heads
    reading 4 key-value heads, all 128 wide — a grid step one key-value head
    and its eight query heads on 128 positions. Under the window of 2,048
    keys the call is named window_attention, its key axis the band's five
    tiles of 512; a full layer keeps the head's whole packed keys and values
    in VMEM, under the budget ``resolve_causal`` tests. Three passes
    (precision=mixed) and one (the control lane), shipped tiles and VMEM
    limit."""
    from video_features_tpu.ops import pallas_attention as kernel
    from video_features_tpu.ops.attention import KERNEL_PASSES, resolve_causal
    precision = {3: 'high', 1: 'default'}[passes]
    assert KERNEL_PASSES[precision] == passes
    assert resolve_causal('tpu', 32768, 128, 128, precision, 32, 4,
                          window) == 'kernel'
    block_q, block_k = kernel.tiles(32768, 8, window)
    assert kernel.resident_tiles(32768, block_q, block_k, window) == ring
    packed = sum(kernel.packed_widths((128,), 128, passes))
    assert 2 * ring * block_k * packed <= kernel.KV_VMEM_BYTES

    def sds(heads):
        return jax.ShapeDtypeStruct((1, 32768, heads, 128), jnp.float32,
                                    sharding=one_chip)

    compiled = jax.jit(lambda q, k, v: kernel.causal_attention(
        q, k, v, 128 ** -0.5, passes, window=window)).lower(
        sds(32), sds(4), sds(4)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    other = {'window_attention', 'causal_attention'} - {name}
    assert f'%{name}' in text and f'%{other.pop()}' not in text
    # q enters and the output leaves as they stand: no copy of either
    assert 'f32[1,32,32768,128]' not in text


@pytest.mark.parametrize('kind', ['sparse', 'window'])
@pytest.mark.parametrize('passes', [3, 1])
def test_causal_attention_compiles_at_dots3_notes_widths(one_chip, passes,
                                                         kind):
    """One window of dots3-note.corpus: 8,192 positions of latent attention
    as column groups with ONE rotary key. A full layer — 128 heads of (128,
    64) / 128 — under the indexer's selection as (8,192, 256) packed words:
    the keep lane, named sparse_attention, a key tile of 1,024 eight 128-lane
    bit planes of its group. A sliding layer — 64 heads of (192, 64) / 128 —
    under a window of 513: the windowed lane with column groups, a score
    tile of 512 positions over the two key tiles of 512 its band crosses.
    Three passes (precision=mixed) and one (the control lane)."""
    from video_features_tpu.ops import pallas_attention as kernel
    from video_features_tpu.ops.attention import KERNEL_PASSES, resolve_causal
    precision = {3: 'high', 1: 'default'}[passes]
    heads, nope = (128, 128) if kind == 'sparse' else (64, 192)
    window = None if kind == 'sparse' else 513
    assert resolve_causal('tpu', 8192, nope + 64, 128, precision, 1, 1,
                          window, kind == 'sparse') == 'kernel'
    assert KERNEL_PASSES[precision] == passes
    if window:
        assert kernel.tiles(8192, 1, window) == (512, 512)
        assert kernel.resident_tiles(8192, 512, 512, window) == 2

    def sds(heads, width):
        return jax.ShapeDtypeStruct((1, 8192, heads, width), jnp.float32,
                                    sharding=one_chip)

    extra = ()
    if kind == 'sparse':
        extra = (jax.ShapeDtypeStruct((1, 8192, 256), jnp.int32,
                                      sharding=one_chip),)

    def attend(q_nope, q_rope, k_nope, k_rope, v, *keep):
        return kernel.causal_attention(
            (q_nope, q_rope), (k_nope, k_rope), v, (nope + 64) ** -0.5,
            passes, window=window, keep=keep[0] if keep else None)

    compiled = jax.jit(attend).lower(
        sds(heads, nope), sds(heads, 64), sds(heads, nope), sds(1, 64),
        sds(heads, 128), *extra).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    name = 'sparse_attention' if kind == 'sparse' else 'window_attention'
    assert f'%{name}' in text and '%causal_attention' not in text


@pytest.fixture(scope='module')
def compiled_scan(one_chip):
    """brumby.corpus's mixer at its widths — 8 key-value heads with 5 query
    heads each, 128-wide, chunks of 512 — over four chunks of one window,
    compiled once a form and ambient precision."""
    from functools import cache

    from video_features_tpu.ops.retention import retention_chunked

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    @cache
    def compiled(precision, passes):
        with jax.default_matmul_precision(precision):
            return jax.jit(
                lambda q, k, v, g: retention_chunked(
                    q, k, v, g, 512, kernel_passes=passes)).lower(
                sds(2048, 8, 5, 128), sds(2048, 8, 128), sds(2048, 8, 128),
                sds(2048, 8)).compile()
    return compiled


@pytest.mark.parametrize('form,precision', [
    ('state', 'high'), ('kernel', 'high'), ('kernel', 'default')])
def test_the_retention_scan_compiles_at_the_cells_widths(compiled_scan, form,
                                                         precision):
    """Either form is one while loop that carries the 8,256 × 128 state.
    XLA's ('state'): no Mosaic call, φ of a chunk's queries as a 676 MB
    buffer, temporaries that still leave the chip's 16 GB to the 8.4 GB of
    parameters. The kernel path, under three passes (precision=mixed) and
    one (the control lane): the two state products as Mosaic calls by name,
    no φ buffer, and less temporary memory than XLA's form takes."""
    from video_features_tpu.ops.attention import KERNEL_PASSES
    from video_features_tpu.ops.retention import resolve_retention
    assert resolve_retention('tpu', 128, 128, 512, precision) == 'kernel'
    xla = compiled_scan('high', None)
    compiled = (xla if form == 'state'
                else compiled_scan(precision, KERNEL_PASSES[precision]))
    text = compiled.as_text()
    assert 'f32[8,8256,128]' in text and ' while(' in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    if form == 'state':
        assert 'tpu_custom_call' not in text
        assert 'f32[8,5,512,8256]' in text
        assert temp < 3 * 2 ** 30
    else:
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert '%retention_read' in text and '%retention_update' in text
        assert 'f32[8,5,512,8256]' not in text and '8256]' not in text
        assert temp < xla.memory_analysis().temp_size_in_bytes // 4


def _while_body(text):
    """The lines of the module's one while body and of every computation it
    calls (its fusions), from the compiled module's text."""
    import re
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r'^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$', line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith('}'):
            name = None
        elif name is not None:
            comps[name].append(line)
    bodies = re.findall(r' while\(.*body=%?([\w.\-]+)', text)
    assert len(bodies) == 1, bodies
    top = comps[bodies[0]]
    called = [line for outer in top
              for inner in re.findall(r'calls=%?([\w.\-]+)', outer)
              for line in comps[inner]]
    return top, called


@pytest.fixture(scope='module')
def refine_text(one_chip):
    """i3d.corpus's `_refine` — 128 pairs at 32×43, 20 updates, three passes
    (precision=mixed) — compiled once; the module's text."""
    from video_features_tpu.models import raft
    from video_features_tpu.transplant.torch2jax import transplant

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.float32, sharding=one_chip), tree)

    params = sds({'update_block': jax.eval_shape(
        lambda: transplant(raft.init_state_dict()))['update_block']})
    fmap = jax.ShapeDtypeStruct((128, 32, 43, 256), jnp.float32,
                                sharding=one_chip)

    def refine(p, fmap1, fmap2, cnet):
        with jax.default_matmul_precision('high'):
            return raft._refine(p, fmap1, fmap2, cnet, 20, 'tpu')

    return jax.jit(refine).lower(params, fmap, fmap, fmap).compile().as_text()


def test_rafts_update_scan_compiles_without_a_two_channel_tensor(refine_text):
    """Four Mosaic calls a lookup by name, and in the while body no
    convolution over or onto an operand whose minor axis is the 2 flow
    components, no channel-minor (B, 32, 43, 2) tensor and so no copy of
    one: the carry is planes, batch on the lanes."""
    import re
    text = refine_text
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert len(re.findall(r'%raft_corr_lookup_lanes[.\d]* = ', text)) == 4
    top, called = _while_body(text)
    convs = [line for line in top + called if ' convolution(' in line]
    assert len(convs) == 11                 # the update block's, all found
    for line in convs:
        shapes = re.findall(r'f32\[([\d,]+)\]', line)
        assert not any(s.endswith(',2') for s in shapes), line
    assert not any('f32[128,32,43,2]{3,' in line for line in top), [
        line for line in top if 'f32[128,32,43,2]{3,' in line]
    assert any('f32[2,128,32,43]{1,0,3,2' in line for line in top)


def test_rafts_lookup_writes_what_convc1_reads(refine_text):
    """The four level calls write ONE (324, rows, 128) buffer in place —
    each later call takes the one before it as its aliased operand — and
    the last call's result is the operand of the fusion that holds
    ``convc1``'s product: no concatenate, transpose, copy or re-tiling
    reshape of the lookup's result stands between (the parent's
    ``%pad_maximum_fusion``, ``%copy`` and ``%reshape`` did). The level-0
    call's blocks fit the VMEM limit the call asks for."""
    import re

    from video_features_tpu.ops import pallas_corr
    text = refine_text
    top, _ = _while_body(text)
    calls = [line for line in top if ' custom-call(' in line
             and 'raft_corr_lookup_lanes' in line.split(' = ')[0]]
    assert len(calls) == 4
    names = [re.match(r'\s*(%[\w.\-]+) = ', line).group(1) for line in calls]
    for prev, line in zip(names, calls[1:]):
        assert re.search(rf'{re.escape(prev)}\)', line), line
    assert all(' = f32[324,1376,128]' in line for line in calls)
    users = [line for line in top if re.search(rf'{re.escape(names[-1])}[,)]',
                                               line)]
    assert len(users) == 1 and ' fusion(' in users[0], users
    called = re.search(r'calls=%?([\w.\-]+)', users[0]).group(1)
    body = _computation(text, called)
    assert any(' convolution(' in line and 'f32[1376,128,256]' in line
               for line in body), body
    for line in body:
        assert not re.search(r' (concatenate|transpose|copy)\(', line), line
    # the level-0 call: the limit it asks for, and what Mosaic used of it
    limit = int(re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                          calls[0]).group(1))
    used = int(re.search(
        r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
        calls[0]).group(1))
    assert pallas_corr.chunks(32, 43) == (32, 43)
    assert limit == pallas_corr.vmem_bytes(32, 43, 9)
    block = 32 * 43 * pallas_corr.TILE * 4
    assert 2 * block <= used <= limit, (block, used, limit)


def _computation(text, name):
    """The lines of one computation of the compiled module's text."""
    import re
    lines, inside = [], False
    for line in text.splitlines():
        head = re.match(r'^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$', line)
        if head:
            inside = head.group(1) == name
        elif line.startswith('}'):
            inside = False
        elif inside:
            lines.append(line)
    return lines


@pytest.mark.parametrize('passes', [3, 1])
def test_the_ssd_scan_compiles_at_granite_micros_widths(one_chip, passes):
    """granite-micro.corpus's Mamba-2 scan at its widths — 64 heads of 64
    over a state of 128 shared by every head, chunks of 256 — over eight
    chunks of a window, through the kernel under three passes
    (precision=mixed) and one (the control lane): one Mosaic call named
    ssd_scan, its head groups whole 128-lane blocks, inside the VMEM limit
    it asks for; and that is what these shapes get on a TPU."""
    from video_features_tpu.ops import pallas_ssd
    from video_features_tpu.ops.attention import KERNEL_PASSES
    from video_features_tpu.ops.ssd import resolve_ssd, ssd_chunked
    precision = {3: 'high', 1: 'default'}[passes]
    assert KERNEL_PASSES[precision] == passes
    assert resolve_ssd('tpu', 32768, 64, 64, 128, 256, precision) == 'kernel'
    assert pallas_ssd.heads_per_step(64, 64) * 64 % pallas_ssd.LANES == 0

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    s = 2048
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(lambda x, dt, a, b, c, d: ssd_chunked(
            x, dt, a, b, c, d, 256, kernel_passes=passes)).lower(
            sds(s, 4096), sds(s, 64), sds(64), sds(s, 128), sds(s, 128),
            sds(64)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert '%ssd_scan' in text
    # x enters and y leaves in the layout the mixer's projections use: no
    # copy of either
    assert 'f32[2048,64,64]' not in text


@pytest.mark.parametrize('passes', [3, 1])
def test_the_index_scores_compile_at_dots3_notes_widths(one_chip, passes):
    """dots3-note.corpus's lightning indexer at its widths — 64 heads of 128
    over 8,192 positions, the 24 query blocks of 256 past index_topk 2,048
    — as ONE Mosaic call named index_scores over their triangle of key
    tiles, under three passes (precision=mixed) and one (the control lane),
    inside the VMEM limit it asks for; and that is what these shapes get on
    a TPU."""
    from video_features_tpu.ops import pallas_index
    from video_features_tpu.ops.attention import KERNEL_PASSES
    from video_features_tpu.ops.sparse_index import (
        resolve_index, scored_blocks,
    )
    precision = {3: 'high', 1: 'default'}[passes]
    assert KERNEL_PASSES[precision] == passes
    assert resolve_index('tpu', 8192, 64, 128, 256, precision) == 'kernel'
    blocks = scored_blocks(8192, 2048, 256)
    assert blocks == list(range(8, 32))

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(lambda q, k, w: pallas_index.index_scores(
        q, k, w, blocks, 256, passes)).lower(
        sds(8192, 64, 128), sds(8192, 128), sds(8192, 64)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert '%index_scores' in text
    # the scores leave as the (rows, keys) matrix top_keys reads
    assert 'f32[6144,8192]' in text


def test_the_topk_threshold_compiles_at_dots3_notes_widths(one_chip):
    """dots3-note.corpus's thresholds — the 24 scored query blocks of 256
    over 8,192 keys, the top 2,048 of each row — as ONE Mosaic call named
    topk_threshold on index_scores' (6,144, 8,192) table, inside the VMEM
    limit it asks for, with no copy of the table; and that is what these
    shapes get on a TPU."""
    from video_features_tpu.ops import pallas_index
    from video_features_tpu.ops.sparse_index import (
        resolve_threshold, scored_blocks,
    )
    assert resolve_threshold('tpu', 8192, 256) == 'kernel'
    blocks = scored_blocks(8192, 2048, 256)
    table = jax.ShapeDtypeStruct((6144, 8192), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda t: pallas_index.topk_threshold(
        t, blocks, 256, 2048)).lower(table).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert '%topk_threshold' in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0
