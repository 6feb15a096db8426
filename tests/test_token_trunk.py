"""The one decoder of the ``lm`` family (``models/token_trunk.py``) as each
model type's row of it meets a benchmark cell's configuration: what the
build notes for the chip (``kernels``), its refusal text (``describe``) and
the parameters' checkpoint order, which fixes the seeded draws. A new
dialect row, or a change to the loop or the shapes walk, has to leave these
as they are. Configs only: nothing is traced."""
import json
from pathlib import Path

import pytest

from video_features_tpu.config import load_config
from video_features_tpu.extract.lm import load_trunk

CONFIGS = Path(__file__).resolve().parent.parent / 'benchmark' / 'configs'

L = 'model.layers.'

CELLS = {
    'joyai-llm-flash-ep4': dict(
        kernels={'causal_attention': 'kernel'},
        describe='5 layers and 64 of 256 experts a layer',
        first=['model.embed_tokens.weight', L + '0.input_layernorm.weight',
               L + '0.self_attn.q_a_proj.weight',
               L + '0.self_attn.q_a_layernorm.weight',
               L + '0.self_attn.q_b_proj.weight',
               L + '0.self_attn.kv_a_proj_with_mqa.weight',
               L + '0.self_attn.kv_a_layernorm.weight',
               L + '0.self_attn.kv_b_proj.weight',
               L + '0.self_attn.o_proj.weight',
               L + '0.post_attention_layernorm.weight'],
        last=[L + '4.post_attention_layernorm.weight',
              L + '4.mlp.gate.weight',
              L + '4.mlp.gate.e_score_correction_bias',
              L + '4.mlp.experts.gate_proj.weight',
              L + '4.mlp.experts.up_proj.weight',
              L + '4.mlp.experts.down_proj.weight',
              L + '4.mlp.shared_experts.gate_proj.weight',
              L + '4.mlp.shared_experts.up_proj.weight',
              L + '4.mlp.shared_experts.down_proj.weight',
              'model.norm.weight']),
    'brumby-14b-l4': dict(
        kernels={'retention': 'kernel', 'retention_chunk': 512},
        describe='4 layers of gated power retention (40 query / 8 key-value '
                 'heads of 128) and a dense SwiGLU of 17408',
        first=['model.embed_tokens.weight', L + '0.input_layernorm.weight',
               L + '0.self_attn.q_proj.weight',
               L + '0.self_attn.k_proj.weight',
               L + '0.self_attn.v_proj.weight',
               L + '0.self_attn.g_proj.weight',
               L + '0.self_attn.g_proj.bias',
               L + '0.self_attn.q_norm.weight',
               L + '0.self_attn.k_norm.weight',
               L + '0.self_attn.o_proj.weight'],
        last=[L + '3.self_attn.g_proj.weight', L + '3.self_attn.g_proj.bias',
              L + '3.self_attn.q_norm.weight',
              L + '3.self_attn.k_norm.weight',
              L + '3.self_attn.o_proj.weight',
              L + '3.post_attention_layernorm.weight',
              L + '3.mlp.gate_proj.weight', L + '3.mlp.up_proj.weight',
              L + '3.mlp.down_proj.weight', 'model.norm.weight']),
    'lfm2-8b-a1b-l8': dict(
        kernels={'causal_attention': 'kernel',
                 'operators': 'conv 6, full_attention 2'},
        describe='8 layers (6 conv + 2 full_attention) and 32 of 32 experts '
                 'in each of the 6 expert layers',
        first=['model.embed_tokens.weight', L + '0.operator_norm.weight',
               L + '0.conv.in_proj.weight', L + '0.conv.conv.weight',
               L + '0.conv.out_proj.weight', L + '0.ffn_norm.weight',
               L + '0.feed_forward.w1.weight', L + '0.feed_forward.w3.weight',
               L + '0.feed_forward.w2.weight',
               L + '1.operator_norm.weight'],
        last=[L + '7.conv.in_proj.weight', L + '7.conv.conv.weight',
              L + '7.conv.out_proj.weight', L + '7.ffn_norm.weight',
              L + '7.feed_forward.gate.weight',
              L + '7.feed_forward.expert_bias',
              L + '7.feed_forward.experts.w1.weight',
              L + '7.feed_forward.experts.w3.weight',
              L + '7.feed_forward.experts.w2.weight',
              'model.embedding_norm.weight']),
    'trinity-mini-ep4-l8': dict(
        kernels={'sliding_attention': 'kernel', 'full_attention': 'kernel',
                 'sliding_window': 2048,
                 'window_tiles': "1240 of the triangle's 8320 (query, key) "
                                 'tiles of 128 x 512',
                 'operators': 'sliding_attention 6, full_attention 2'},
        describe='8 layers (6 sliding_attention + 2 full_attention) and 32 '
                 'of 128 experts in each of the 6 expert layers',
        first=['model.embed_tokens.weight', L + '0.input_layernorm.weight',
               L + '0.self_attn.q_proj.weight',
               L + '0.self_attn.k_proj.weight',
               L + '0.self_attn.v_proj.weight',
               L + '0.self_attn.gate_proj.weight',
               L + '0.self_attn.q_norm.weight',
               L + '0.self_attn.k_norm.weight',
               L + '0.self_attn.o_proj.weight',
               L + '0.post_attention_layernorm.weight'],
        last=[L + '7.mlp.router.gate.weight', L + '7.mlp.expert_bias',
              L + '7.mlp.experts.gate_proj.weight',
              L + '7.mlp.experts.up_proj.weight',
              L + '7.mlp.experts.down_proj.weight',
              L + '7.mlp.shared_experts.gate_proj.weight',
              L + '7.mlp.shared_experts.up_proj.weight',
              L + '7.mlp.shared_experts.down_proj.weight',
              L + '7.post_mlp_layernorm.weight', 'model.norm.weight']),
    'dots3-note-prev-ep32-l5': dict(
        kernels={'sparse_attention': 'kernel', 'window_attention': 'kernel',
                 'index_scores': 'kernel', 'topk_threshold': 'kernel',
                 'layers': 'full_attention 2, sliding_attention 3'},
        describe='5 layers (2 full_attention + 3 sliding_attention) and 8 of '
                 '256 experts a layer',
        first=['model.embed_tokens.weight', L + '0.input_layernorm.weight',
               L + '0.self_attn.q_a_proj.weight',
               L + '0.self_attn.q_a_layernorm.weight',
               L + '0.self_attn.q_b_proj.weight',
               L + '0.self_attn.kv_a_proj_with_mqa.weight',
               L + '0.self_attn.kv_a_layernorm.weight',
               L + '0.self_attn.kv_b_proj.weight',
               L + '0.self_attn.o_proj.weight',
               L + '0.self_attn.gate_proj.weight'],
        last=[L + '4.post_attention_layernorm.weight',
              L + '4.mlp.gate.weight',
              L + '4.mlp.gate.e_score_correction_bias',
              L + '4.mlp.experts.gate_proj.weight',
              L + '4.mlp.experts.up_proj.weight',
              L + '4.mlp.experts.down_proj.weight',
              L + '4.mlp.shared_experts.gate_proj.weight',
              L + '4.mlp.shared_experts.up_proj.weight',
              L + '4.mlp.shared_experts.down_proj.weight',
              'model.norm.weight']),
    'granite-4.0-h-micro-l20': dict(
        kernels={'causal_attention': 'kernel', 'ssd': 'kernel',
                 'ssd_chunk': 256, 'operators': 'mamba 18, attention 2'},
        describe='20 layers (18 mamba + 2 attention) and a dense SwiGLU of '
                 '8192',
        first=['model.embed_tokens.weight', L + '0.input_layernorm.weight',
               L + '0.mamba.in_proj.weight', L + '0.mamba.conv1d.weight',
               L + '0.mamba.conv1d.bias', L + '0.mamba.dt_bias',
               L + '0.mamba.A_log', L + '0.mamba.D',
               L + '0.mamba.norm.weight', L + '0.mamba.out_proj.weight'],
        last=[L + '19.mamba.conv1d.bias', L + '19.mamba.dt_bias',
              L + '19.mamba.A_log', L + '19.mamba.D',
              L + '19.mamba.norm.weight', L + '19.mamba.out_proj.weight',
              L + '19.post_attention_layernorm.weight',
              L + '19.shared_mlp.input_linear.weight',
              L + '19.shared_mlp.output_linear.weight',
              'model.norm.weight']),
}


@pytest.mark.parametrize('config', sorted(CELLS))
def test_each_model_type_at_its_cell_keeps_its_notes_and_parameter_order(
        config):
    body = json.loads((CONFIGS / f'{config}.json').read_text())
    args = load_config('lm', overrides=dict(
        body['overrides'], video_paths=['x.mp4'], device='cpu'))
    trunk = load_trunk(args.get('model_type'))
    cfg = trunk.TrunkConfig.from_args(args)
    assert cfg.model_type == body['model_type']
    window_ids = int(args.stack_size) * int(args.patch_grid) ** 2
    want = CELLS[config]
    assert trunk.kernels(cfg, 'tpu', window_ids, 'high') == want['kernels']
    assert trunk.describe(cfg) == want['describe']
    names = list(trunk.param_shapes(cfg))
    assert names[:10] == want['first']
    assert names[-10:] == want['last']
