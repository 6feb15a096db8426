"""Config system: YAML defaults, dotlist merge, sanity_check behavior parity."""
import os

import pytest

from video_features_tpu.config import (
    Config, form_list_from_user_input, load_config, parse_dotlist, sanity_check,
)


def _mk_video(tmp_path, name='vid.mp4'):
    p = tmp_path / name
    p.write_bytes(b'\x00')
    return str(p)


def test_parse_dotlist_yaml_typing():
    cfg = parse_dotlist([
        'feature_type=i3d', 'stack_size=24', 'extraction_fps=null',
        'keep_tmp_files=true', "video_paths=['a.mp4','b.mp4']",
    ])
    assert cfg.feature_type == 'i3d'
    assert cfg.stack_size == 24 and isinstance(cfg.stack_size, int)
    assert cfg.extraction_fps is None
    assert cfg.keep_tmp_files is True
    assert cfg.video_paths == ['a.mp4', 'b.mp4']


def test_load_config_defaults_and_override(tmp_path):
    v = _mk_video(tmp_path)
    args = load_config('i3d', overrides={'video_paths': v, 'stack_size': 24,
                                         'device': 'cpu'})
    assert args.feature_type == 'i3d'
    assert args.stack_size == 24
    assert args.step_size == 16  # YAML default survives
    # path rewriting appends feature_type
    assert args.output_path.endswith(os.path.join('output', 'i3d'))
    assert args.tmp_path.endswith(os.path.join('tmp', 'i3d'))


def test_model_name_appended_with_slash_replaced(tmp_path):
    v = _mk_video(tmp_path)
    args = load_config('clip', overrides={'video_paths': v, 'device': 'cpu'})
    assert args.output_path.endswith(os.path.join('output', 'clip', 'ViT-B_32'))


def test_unknown_feature_type():
    with pytest.raises(NotImplementedError):
        load_config('pwc2')


def test_sanity_rejects_missing_paths():
    with pytest.raises(AssertionError):
        load_config('i3d', overrides={'device': 'cpu'})


def test_sanity_rejects_duplicate_stems(tmp_path):
    a = tmp_path / 'a';  a.mkdir()
    b = tmp_path / 'b';  b.mkdir()
    v1 = _mk_video(a, 'same.mp4')
    v2 = _mk_video(b, 'same.mp4')
    with pytest.raises(AssertionError):
        load_config('resnet', overrides={'video_paths': [v1, v2], 'device': 'cpu'})


def test_sanity_rejects_small_i3d_stack(tmp_path):
    v = _mk_video(tmp_path)
    with pytest.raises(AssertionError):
        load_config('i3d', overrides={'video_paths': v, 'stack_size': 4,
                                      'device': 'cpu'})


def test_sanity_rejects_pwc(tmp_path):
    v = _mk_video(tmp_path)
    with pytest.raises(NotImplementedError):
        load_config('i3d', overrides={'video_paths': v, 'flow_type': 'pwc',
                                      'device': 'cpu'})


def test_sanity_rejects_fps_and_total(tmp_path):
    v = _mk_video(tmp_path)
    with pytest.raises(AssertionError):
        load_config('resnet', overrides={'video_paths': v, 'extraction_fps': 5,
                                         'extraction_total': 10, 'device': 'cpu'})


def test_sanity_rejects_same_out_and_tmp(tmp_path):
    v = _mk_video(tmp_path)
    with pytest.raises(AssertionError):
        load_config('resnet', overrides={'video_paths': v, 'output_path': './x',
                                         'tmp_path': './x', 'device': 'cpu'})


def test_timm_requires_model_name(tmp_path):
    v = _mk_video(tmp_path)
    with pytest.raises(AssertionError):
        load_config('timm', overrides={'video_paths': v, 'device': 'cpu'})


def test_device_never_leaks_cuda(tmp_path):
    # 'cuda:0' (torch-style) means "the accelerator". This lane has none
    # (conftest pins cpu), and an accelerator request must RAISE naming
    # what was found — never carry on on the CPU (the yml default is
    # 'tpu', so the same holds with no device override at all).
    v = _mk_video(tmp_path)
    for overrides in ({'device': 'cuda:0'}, {'device': 'tpu'}, {}):
        with pytest.raises(RuntimeError, match=r"only platform\(s\) \['cpu'\]"):
            load_config('resnet', overrides={'video_paths': v, **overrides})


def test_device_cpu_stays_cpu(tmp_path):
    v = _mk_video(tmp_path)
    args = load_config('resnet', overrides={'video_paths': v, 'device': 'cpu'})
    assert args.device == 'cpu'


def test_form_list_from_file(tmp_path):
    v1 = _mk_video(tmp_path, 'a.mp4')
    v2 = _mk_video(tmp_path, 'b.mp4')
    listfile = tmp_path / 'list.txt'
    listfile.write_text(f'{v1}\n\n{v2}\n')
    paths = form_list_from_user_input(None, str(listfile), to_shuffle=False)
    assert paths == [v1, v2]


def test_config_attr_access():
    c = Config(a=1)
    assert c.a == 1
    c.b = 2
    assert c['b'] == 2
    with pytest.raises(AttributeError):
        _ = c.missing


def test_precision_validated(tmp_path):
    v = _mk_video(tmp_path)
    args = load_config('resnet', overrides={
        'video_paths': v, 'device': 'cpu', 'precision': 'default'})
    assert args.precision == 'default'
    # ValueError (not assert) so validation survives `python -O`
    with pytest.raises(ValueError, match='precision'):
        load_config('resnet', overrides={
            'video_paths': v, 'device': 'cpu', 'precision': 'fp8'})


def test_pack_fallback_warns_off_stdout(tmp_path, capsys):
    """The pack_across_videos degradations must go through warnings.warn
    (stderr), NOT print: with on_extraction=print the feature stream owns
    stdout and an interleaved WARNING line breaks its parsers."""
    v = _mk_video(tmp_path)
    with pytest.warns(UserWarning, match='not implemented for vggish'):
        args = load_config('vggish', overrides={
            'video_paths': v, 'device': 'cpu', 'pack_across_videos': True})
    assert args['pack_across_videos'] is False
    assert 'WARNING' not in capsys.readouterr().out

    with pytest.warns(UserWarning, match='show_pred is incompatible'):
        args = load_config('resnet', overrides={
            'video_paths': v, 'device': 'cpu', 'model_name': 'resnet18',
            'pack_across_videos': True, 'show_pred': True})
    assert args['pack_across_videos'] is False
    assert 'WARNING' not in capsys.readouterr().out


def test_precision_reaches_extractor(tmp_path):
    from video_features_tpu.registry import create_extractor
    v = _mk_video(tmp_path)
    args = load_config('resnet', overrides={
        'video_paths': v, 'device': 'cpu', 'batch_size': 2,
        'precision': 'default', 'compilation_cache_dir': None})
    ex = create_extractor(args)
    assert ex.precision == 'default'
