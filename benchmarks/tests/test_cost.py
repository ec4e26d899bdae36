"""The FLOPs and bytes functions against hand arithmetic for the three
configurations (the products are written out so a reader can redo them)."""

import itertools

import pytest

from benchmarks.lib import cost, cost_sdar, spec, trace_reduce as tr
from benchmarks.lib.family import AttentionCall, causal, kind_of
from benchmarks.lib.peaks import PEAKS
from benchmarks.lib.record import RunRecord


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
    # One sequence of 8192, 32 heads of 128 over 8 K/V heads, causal: half
    # the square.
    call = causal(32, 8, 128)
    square = 32 * 8192 * 8192 * 128 // 2
    q_like = 32 * 8192 * 128 * 2                     # one bf16 operand
    kv_like = 8 * 8192 * 128 * 2
    assert cost.attention_call_cost("fwd", 1, call, 8192) == (
        4 * square, 2 * q_like + 2 * kv_like)        # QK^T, PV; q o, k v
    assert cost.attention_call_cost("bwd", 1, call, 8192) == (
        10 * square, 4 * q_like + 4 * kv_like)       # five matmuls
    peaks = PEAKS["TPU v5 lite"]
    seconds, bound = cost.least_time(4 * square, 4 * q_like, peaks.flops,
                                     peaks.hbm_bw)
    assert bound == "compute"
    assert seconds == pytest.approx(549_755_813_888 / 197e12)
    # Eight sequences of 1024 move the same bytes for an eighth of the work:
    # 0.349 ms of compute against 0.205 ms of memory (0.328 with k and v
    # counted at the query heads, as until PR 51), still compute.
    flops, nbytes = cost.attention_call_cost("fwd", 8, call, 1024)
    assert nbytes == 2 * q_like + 2 * kv_like and flops == 4 * square / 8
    assert cost.least_time(flops, nbytes, peaks.flops, peaks.hbm_bw)[1] \
        == "compute"
    with pytest.raises(ValueError):
        cost.attention_call_cost("sideways", 1, call, 1)


#: What the parent of PR 51 charged one forward call of each cell, written
#: down before its three functions went (``lib/cost.py:attention_call_cost``
#: at ``Family.attention_heads``, and in the two cells of a reader of their
#: own ``cost_sdar`` / ``cost_joyai.attention_call_cost``): (FLOPs, bytes);
#: the fused backward was 2.5 times the FLOPs (in ``joyai-ep16-s8192`` 2.6:
#: 1664 / 640) and twice the bytes.  The FLOPs stand to the digit.  The bytes
#: stand where the parent counted k and v at their own heads; where it
#: counted them at the query heads the third number is today's (q and o at
#: the query heads, k and v at the K/V heads the kernel is handed), and every
#: such call stays compute-bound on the v5e.
PARENT = {
    "mistral7b-s8192": (549_755_813_888, 268_435_456, 167_772_160),
    "mistral7b-s1024": (68_719_476_736, 268_435_456, 167_772_160),
    "gpt2xl-s1024": (53_687_091_200, 209_715_200, None),
    "olmoe-s4096": (137_438_953_472, 134_217_728, None),
    "mistral7b-fsdp4-s4096": (137_438_953_472, 134_217_728, 83_886_080),
    "sdar-ep8-s8192": (1_100_048_498_688, 301_989_888, None),
    "nemotron-ep16-s8192": (1_099_511_627_776, 536_870_912, 285_212_672),
    "solar-open2-ep40-tp8": (137_438_953_472, 67_108_864, 37_748_736),
    "joyai-ep16-s8192": (687_194_767_360, 335_544_320, None),
}


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_the_kinds_restate_what_the_parent_charged(cell):
    """Each cell's kind (the first the family states: ``sdar``'s is the one
    call it makes today) charges what the parent's functions charged."""
    held = spec.load_cell(spec.load_benchmark(), cell)
    config, traffic = held["config_file"], held["traffic_file"]
    S, B = traffic["seq_len"], traffic["seqs_per_chip"]
    call = spec.load_module("models", config["family"]).build(
        config, S).attention_calls[0]
    flops, parent_bytes, todays = PARENT[cell]
    fwd = cost.attention_call_cost("fwd", B, call, S)
    bwd = cost.attention_call_cost("bwd", B, call, S)
    assert fwd == (flops, todays or parent_bytes)
    assert bwd == (flops * (2.6 if "joyai" in cell else 2.5), 2 * fwd[1])
    assert (todays is not None) == (call.kv_heads != call.q_heads
                                    and "sdar" not in cell)
    for one in (fwd, bwd):
        assert cost.least_time(*one, 197e12, 819e9)[1] == "compute"


def test_sdars_two_covers_have_one_area():
    config = spec.load_json(spec.BENCH_DIR, "configs",
                            "sdar-30b-a3b-l6-ep8.json")
    whole, noised, clean = spec.load_module("models", "sdar").build(
        config, 8192).attention_calls
    for S in (8192, 1024, 64):
        assert noised.pairs(S) + clean.pairs(S) == whole.pairs(S) \
            == cost_sdar.mask_area(S, config["block_length"])
    # told apart by their lengths alone
    assert len({(c.q_len, c.kv_len) for c in (whole, noised, clean)}) == 3
    assert {c.shapes(8192)[0][0] for c in (whole, noised, clean)} == {32}


# --------------------------------------------- a step that runs two masks
S = 8192
WINDOW = 512
#: Laguna's shapes: a full layer has 48 query heads and a causal mask, a
#: sliding layer 72 and a band of 512 keys; 8 K/V heads of 128 in both.
FULL = causal(48, 8, 128)
SLIDING = AttentionCall(
    "window512", 72, 8, 128, 128,
    pairs=lambda S: S * WINDOW - WINDOW * (WINDOW - 1) / 2)
PEAKS_V5E = PEAKS["TPU v5 lite"]


def _operands(call, way, rows=1):
    """A call's operands as the splash kernel's line has them."""
    lead = [rows] if rows > 1 else []
    q, k, v = (["bf16", lead + list(dims)] for dims in call.shapes(S))
    more = [["f32", lead + [call.q_heads, 8, S]], q] if way == "bwd" else []
    return [["s8", [1, 8, 8]], ["s8", [1, 8, 8]], q, k, v] + more \
        + [["s32", [8, S]]]


def _made_up_run(order, slow=1.0):
    """A step of two full and three sliding layers, a forward and a fused
    backward call each, whose events take ``slow`` times their least time,
    in the order given."""
    layers = [FULL, FULL, SLIDING, SLIDING, SLIDING]
    calls = [(f"splash_mha_{'fwd_residuals' if way == 'fwd' else 'dkv_no_residuals'}.{i}",
              call, way)
             for i, (call, way) in enumerate(
                 itertools.product(layers, ("fwd", "bwd")))]
    calls = [calls[i] for i in order]
    events, at = [], 0.0
    for name, call, way in calls:
        least = cost.least_time(*cost.attention_call_cost(way, 1, call, S),
                                PEAKS_V5E.flops, PEAKS_V5E.hbm_bw)[0]
        events.append(tr.Event(name, at, slow * least))
        at += slow * least
    trace = tr.Trace({0: tr.Device(0, ops=events)}, [], {})
    return RunRecord(
        cell={}, chips=1, peaks=PEAKS_V5E, tokens_per_step=S,
        flops_per_step=1.0, seq_len=S, attention_calls=(FULL, SLIDING),
        trace=trace, steady=(0.0, at + 1.0, 1, [at]),
        hlo={"mosaic": {name: "jit(step)/attn_kernel/pallas_call"
                        for name, _, _ in calls},
             "operands": {name: _operands(call, way)
                          for name, call, way in calls}})


@pytest.mark.parametrize("order", [
    list(range(10)), list(range(9, -1, -1)), [4, 0, 9, 2, 7, 5, 1, 8, 3, 6]],
    ids=["as_made", "reversed", "shuffled"])
def test_a_step_of_two_masks_reads_the_share_of_its_own_work(order):
    """Events that each take exactly their own least time read 100.0,
    whatever their order.  (The parent's reader charged every call the full
    causal one at one pair of heads and head dimension: at 48 heads all five
    calls a direction the full call's cost, 5 / (2 + 3 x 0.1875) = 195 % of
    this run, a sliding call being 72 / 48 x 0.125 = 0.1875 of a full one.)"""
    reader = spec.load_module("layer_metrics", "kernels.splash_roofline")
    assert reader.read(_made_up_run(order)) == pytest.approx(100.0, abs=1e-9)
    assert reader.read(_made_up_run(order, slow=2.0)) \
        == pytest.approx(50.0, abs=1e-9)
    note = reader.describe(_made_up_run(order, slow=4.0))
    assert sorted(note) == ["causal.bwd", "causal.fwd", "window512.bwd",
                            "window512.fwd"]
    assert [note[k]["calls_a_step"] for k in sorted(note)] == [2, 2, 3, 3]
    assert all(row["roofline"] == pytest.approx(25.0)
               and row["bound_by"] == "compute" for row in note.values())
    full = cost.attention_call_cost("fwd", 1, FULL, S)[0]
    assert cost.attention_call_cost("fwd", 1, SLIDING, S)[0] / full \
        == pytest.approx(0.1875, rel=0.04)       # less the band's corner
    assert 5 / (2 + 3 * 0.1875) == pytest.approx(1.95, abs=0.005)


def test_a_call_no_kind_claims_raises_and_names_it():
    """A splash call of 64 query heads, which neither kind states: never a
    silent causal charge, never a call left out of the sum."""
    reader = spec.load_module("layer_metrics", "kernels.splash_roofline")
    run = _made_up_run(list(range(10)))
    stray = "splash_mha_fwd_residuals.77"
    run.hlo["mosaic"][stray] = "jit(step)/attn_kernel/pallas_call"
    run.hlo["operands"][stray] = _operands(causal(64, 8, 128), "fwd")
    run.trace.first.ops.append(tr.Event(stray, 0.5, 1e-3))
    with pytest.raises(ValueError, match=r"splash_mha_fwd_residuals\.77.*"
                       r"no kind.*causal.*window512"):
        reader.read(run)
    with pytest.raises(ValueError, match=r"splash_mha_fwd_residuals\.77"):
        reader.describe(run)
    # a kernel whose line shows no shapes is claimed by nobody either
    run.hlo["operands"][stray] = []
    with pytest.raises(ValueError, match="no kind"):
        reader.read(run)


def test_kinds_are_told_apart_by_shapes_rows_and_scope():
    calls = (FULL, SLIDING)
    assert kind_of(calls, S, "a", _operands(SLIDING, "bwd", rows=4), "") \
        == (SLIDING, 4)
    assert kind_of(calls, S, "a", _operands(FULL, "fwd"), "") == (FULL, 1)
    # two kinds of one shape need a named scope of the program each
    twins = (causal(48, 8, 128),
             AttentionCall("window", 48, 8, 128, 128, pairs=SLIDING.pairs,
                           scope="/sliding/"))
    with pytest.raises(ValueError, match=r"claimed by \['causal', 'window'\]"):
        kind_of(twins, S, "a", _operands(FULL, "fwd"),
                "jit(step)/sliding/attn_kernel/pallas_call")
    twins = (AttentionCall("causal", 48, 8, 128, 128,
                           pairs=cost.causal_pairs, scope="/full/"),
             twins[1])
    assert kind_of(twins, S, "a", _operands(FULL, "fwd"),
                   "jit(step)/sliding/attn_kernel/pallas_call")[0].name \
        == "window"


def test_hlo_report_counts_what_the_module_holds():
    from benchmarks.lib.compile_watch import hlo_report

    hlo = """
  %splash_mha_fwd_residuals.15 = (f32[8]) custom-call(%a, %q, %k, %v), custom_call_target="tpu_custom_call", operand_layout_constraints={s8[1,2,2]{2,1,0}, bf16[8,32,1024,128]{3,2,1,0}, bf16[8,8,1024,128]{3,2,1,0}, bf16[8,8,1024,128]{3,2,1,0}, s32[]}, frontend_attributes={kernel_metadata={
"xprof_metadata":"{\"block_q\": 1024}"
}}, metadata={op_name="jit(step)/attn_kernel/pallas_call" stack_frame_id=187}, backend_config={}
  %pallas_call.62 = f32[8] get-tuple-element(%splash_mha_fwd_residuals.15), index=0, metadata={op_name="jit(step)/other"}
  %gmm.1 = f32[8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/experts/pallas_call"}
  %custom-call.3 = f32[8] custom-call(%a), custom_call_target="Sharding"
  %all-gather-start.1 = (f32[4], f32[16]) all-gather-start(%p), dimensions={0}
  %all-gather-done.1 = f32[16] all-gather-done(%all-gather-start.1)
  %all-gather.2 = f32[16] all-gather(%q), dimensions={0}
  %all-reduce.4 = f32[] all-reduce(%r), to_apply=%add
  %fusion.388 = f32[4] fusion(%g), kind=kCustom, calls=%all-reduce-scatter.5
  %fusion.9 = f32[4] fusion(%g), kind=kLoop, calls=%fused_computation.1
"""
    report = hlo_report(hlo)
    # a kernel's metadata holds newlines: its op_name stands two lines on
    assert report["mosaic"] == {
        "splash_mha_fwd_residuals.15": "jit(step)/attn_kernel/pallas_call",
        "gmm.1": "jit(step)/experts/pallas_call"}
    assert report["operands"] == {
        "splash_mha_fwd_residuals.15": [
            ["s8", [1, 2, 2]], ["bf16", [8, 32, 1024, 128]],
            ["bf16", [8, 8, 1024, 128]], ["bf16", [8, 8, 1024, 128]],
            ["s32", []]],
        "gmm.1": []}
    assert kind_of((causal(32, 8, 128),), 1024, "splash_mha_fwd_residuals.15",
                   report["operands"]["splash_mha_fwd_residuals.15"],
                   report["mosaic"]["splash_mha_fwd_residuals.15"])[1] == 8
    assert report["collectives"] == {
        "all-gather": 2, "all-reduce": 1, "reduce-scatter": 1,
        "all-to-all": 0, "collective-permute": 0}
