"""The yardstick's arithmetic, pinned to hand counts at the cells' shapes."""
import json

import pytest

import modelops
import spec

BERT = spec.load_json(spec.BENCH / "configs" / "bert-base-samp.json")
QWEN = spec.load_json(spec.BENCH / "configs" / "qwen2-0.5b-samp.json")


def test_quant_linear_counts_at_bert_and_qwen_shapes():
    ql = spec.kernel_counts("quant_linear")
    # bert-base q projection over a 32 x 512 micro-batch, int8 out
    assert ql.ops(32 * 512, 768, 768) == 19_327_352_832
    assert ql.bytes_moved(32 * 512, 768, 768, 1) == 25_755_648
    # qwen2-0.5b gate projection over 32 decode slots, float32 out
    assert ql.ops(32, 896, 4864) == 278_921_216
    assert ql.bytes_moved(32, 896, 4864, 4) == 5_009_408


def test_flash_attention_counts_at_bert_shape():
    fa = spec.kernel_counts("flash_attention")
    assert fa.ops(32, 12, 512, 512, 64) == 25_769_803_776
    assert fa.bytes_moved(32, 12, 512, 512, 64, 1) == 50_397_184


def test_decode_attention_counts_at_qwen_shape():
    da = spec.kernel_counts("decode_attention")
    # 32 slots at 400 tokens of context each
    assert da.ops(32 * 400, 14, 64) == 45_875_200
    assert da.bytes_moved(32 * 400, 32, 14, 2, 64) == 3_710_976


def test_model_ops_of_a_bert_request():
    i8, other = modelops.encoder_request(BERT, 128)
    assert i8 == 22_347_251_712           # every GEMM and attention, int8
    assert other == 1_202_688             # pooler + 15 classes


def test_model_ops_of_a_qwen_token():
    i8, other = modelops.decode_token(QWEN, 99)
    assert i8 == 627_572_736              # 24 layers of SwiGLU, int8
    assert other == 368_951_296           # projections, attention, head


def test_least_time_weighs_int8_and_float_at_their_peaks():
    peaks = spec.peaks("TPU v5 lite")
    assert modelops.least_seconds((393e12, 197e12), peaks) == 2.0


def test_unknown_device_kind_raises():
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")


def test_peaks_table_names_its_source():
    table = json.loads((spec.BENCH / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
