"""The FLOPs and bytes functions against hand arithmetic for the three
configurations (the products are written out so a reader can redo them)."""

import pytest

from benchmarks.lib import cost, spec
from benchmarks.lib.peaks import PEAKS


def config(name):
    return spec.load_json(spec.BENCH_DIR, "configs", name + ".json")


MISTRAL_LAYER = (4096 * 4096          # wq
                 + 2 * 4096 * 1024    # wk, wv: 8 KV heads of 128
                 + 4096 * 4096        # wo
                 + 3 * 4096 * 14336)  # gate, up, down
MISTRAL_HEAD = 32768 * 4096
GPT2XL_LAYER = 12 * 1600 * 1600       # qkv 3, out 1, mlp 4 + 4
GPT2XL_HEAD = 50257 * 1600            # tied, published vocabulary


def test_layer_sizes_by_hand():
    assert MISTRAL_LAYER == 218_103_808
    assert GPT2XL_LAYER == 30_720_000


@pytest.mark.parametrize("name, fn, want", [
    ("mistral-7b-v0.3-l2", cost.llama_matmul_params,
     2 * MISTRAL_LAYER + MISTRAL_HEAD),
    ("mistral-7b-v0.3-l12-fsdp4", cost.llama_matmul_params,
     12 * MISTRAL_LAYER + MISTRAL_HEAD),
    ("gpt2-xl-l12", cost.gpt2_matmul_params, 12 * GPT2XL_LAYER + GPT2XL_HEAD),
])
def test_matmul_params(name, fn, want):
    assert fn(config(name)) == want


@pytest.mark.parametrize("params, layers, width, seq, want", [
    # 6 x 570,425,344 + 6 x 2 x 8192 x 4096
    (570_425_344, 2, 4096, 8192, 3_825_205_248),
    (570_425_344, 2, 4096, 1024, 3_472_883_712),
    # 6 x 449,051,200 + 6 x 12 x 1024 x 1600
    (449_051_200, 12, 1600, 1024, 2_812_272_000),
    # 6 x 2,751,463,424 + 6 x 12 x 4096 x 4096
    (2_751_463_424, 12, 4096, 4096, 17_716_740_096),
])
def test_model_flops_per_token(params, layers, width, seq, want):
    assert cost.model_flops_per_token(params, layers, width, seq) == want


def test_attention_call_cost_and_bound():
    # One sequence of 8192, 32 heads of 128, causal: half the square.
    square = 32 * 8192 * 8192 * 128 // 2
    tensor = 32 * 8192 * 128 * 2                     # one bf16 operand
    assert cost.attention_call_cost("fwd", 1, 32, 8192, 128) == (
        4 * square, 4 * tensor)                      # QK^T, PV; q k v o
    assert cost.attention_call_cost("bwd", 1, 32, 8192, 128) == (
        10 * square, 8 * tensor)                     # five matmuls
    peaks = PEAKS["TPU v5 lite"]
    seconds, bound = cost.least_time(4 * square, 4 * tensor, peaks.flops,
                                     peaks.hbm_bw)
    assert bound == "compute"
    assert seconds == pytest.approx(549_755_813_888 / 197e12)
    # Eight sequences of 1024 move the same bytes for an eighth of the work:
    # 0.349 ms of compute against 0.328 ms of memory, still compute, just.
    flops, nbytes = cost.attention_call_cost("fwd", 8, 32, 1024, 128)
    assert nbytes == 4 * tensor and flops == 4 * square / 8
    assert cost.least_time(flops, nbytes, peaks.flops, peaks.hbm_bw)[1] \
        == "compute"
    with pytest.raises(ValueError):
        cost.attention_call_cost("sideways", 1, 1, 1, 1)


def test_hlo_report_counts_what_the_module_holds():
    from benchmarks.lib.compile_watch import hlo_report

    hlo = """
  %splash_mha_fwd_residuals.15 = (f32[8]) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call"}
  %custom-call.3 = f32[8] custom-call(%a), custom_call_target="Sharding"
  %all-gather-start.1 = (f32[4], f32[16]) all-gather-start(%p), dimensions={0}
  %all-gather-done.1 = f32[16] all-gather-done(%all-gather-start.1)
  %all-gather.2 = f32[16] all-gather(%q), dimensions={0}
  %all-reduce.4 = f32[] all-reduce(%r), to_apply=%add
  %fusion.388 = f32[4] fusion(%g), kind=kCustom, calls=%all-reduce-scatter.5
  %fusion.9 = f32[4] fusion(%g), kind=kLoop, calls=%fused_computation.1
"""
    report = hlo_report(hlo)
    assert report["mosaic"] == {
        "splash_mha_fwd_residuals.15": "jit(step)/pallas_call"}
    assert report["collectives"] == {
        "all-gather": 2, "all-reduce": 1, "reduce-scatter": 1,
        "all-to-all": 0, "collective-permute": 0}
