"""FLOP and byte functions against hand counts at a small shape."""
import pytest

from yardstick import flops, hlo, weights

SMALL = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
         "intermediate_size": 8, "vocab_size": 10, "num_hidden_layers": 1, "torch_dtype": "bfloat16"}


def test_param_count_by_hand():
    # embed 10*4, wq 4*2*2, wk 4*1*2, wv 4*1*2, wo 2*2*4, q/k norm 2+2, norms 4+4,
    # mlp 3*4*8, final norm 4
    assert weights.param_count(SMALL) == 40 + 16 + 8 + 8 + 16 + 4 + 8 + 96 + 4


def test_train_flops_by_hand():
    # per layer and token: 2*(4*(2+2*1)*2 + 2*2*4 + 3*4*8) = 288 matmul FLOPs;
    # causal attention over S=3: mean (3+1)/2 keys, 4*H*hd = 16 per key -> 32;
    # tied head 2*4*10 = 80; forward 400, training 3x
    assert flops.train_flops_per_token(SMALL, 3) == 1200


def test_serve_flops_and_bytes_by_hand():
    # prefill of 3 tokens: 3*288 matmul + 16 * (1+2+3) attention + one head 80
    assert flops.prefill_flops(SMALL, 3) == 864 + 96 + 80
    # decode with 5 keys: 288 + 16*5 + 80
    assert flops.decode_flops(SMALL, 5) == 288 + 80 + 80
    # all weights once (200 params x 2 B) + 1 layer x (K and V) x 1 head x 2 x 2 B per token
    assert flops.kv_bytes_per_token(SMALL) == 8
    assert flops.decode_tick_bytes(SMALL, [3, 4]) == 200 * 2 + 8 * 7


def test_qwen3_widths():
    q17 = {"hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 8, "head_dim": 128,
           "intermediate_size": 6144, "vocab_size": 151936, "num_hidden_layers": 4}
    assert flops.train_flops_per_token(q17, 512) == pytest.approx(3.10e9, rel=0.01)


HLO = """HloModule m, entry_computation_layout={()}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8]{0} get-tuple-element(%p), index=1
  %cp = f32[8]{0} collective-permute(%x), source_target_pairs={{0,1},{1,0}}
  %k = (u8[2,128]{1,0}, bf16[4,128]{1,0}) custom-call(%x, %x), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4,128]{1,0}, bf16[4,128]{1,0}}, backend_config={}
  ROOT %t = (s32[], f32[8]) tuple(%p)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %c = pred[] constant(true)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %g = f32[16]{0} all-gather(%a), dimensions={0}
  %w = (s32[], f32[8]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %r = f32[8]{0} get-tuple-element(%w), index=1
}
"""


def test_hlo_counts():
    # the kernel reads f32[4,128] + bf16[4,128] and writes u8[2,128] + bf16[4,128]
    assert hlo.custom_call_bytes(HLO) == {"k": 2048 + 1024 + 256 + 1024}
