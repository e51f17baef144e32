"""The yardstick's operation and byte counts against direct counts."""

import pytest

from benchmark import peaks, work


def test_kv_bytes_equal_the_programs_ledger_at_its_tile():
    from synapseml_tpu.models.llm.pallas_attn import paged_read_bytes
    spans = [1, 17, 255, 256, 257, 1408, 2048, 0]
    for tile in (128, 256, 512):
        assert work.paged_kv_bytes(spans, 8, 128, 2, layers=16, tile=tile) == \
            paged_read_bytes(spans, tile, 8, 128, 2, num_layers=16)


def test_needed_kv_bytes_are_every_live_key_and_value_once():
    assert work.paged_kv_bytes([100, 28], 8, 128, 2) == 2 * 128 * 8 * 128 * 2
    assert work.paged_kv_bytes([100], 8, 128, 2, tile=256) > \
        work.paged_kv_bytes([100], 8, 128, 2)


def test_mistral_layer_by_hand():
    # q 4096x4096, k and v 4096x1024, o 4096x4096; gate, up, down 4096x14336
    by_hand = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert by_hand == 218_103_808
    assert work.decoder_layer_params(4096, 32, 8, 128, 14336) == by_hand
    assert work.decoder_token_flops(16, 4096, 32, 8, 128, 14336) == \
        2.0 * 16 * by_hand
    assert work.lm_head_flops(4096, 32768) == 2.0 * 4096 * 32768


def test_bert_layer_by_hand():
    by_hand = 4 * 768 * 768 + 2 * 768 * 3072
    assert work.encoder_layer_params(768, 3072) == by_hand == 7_077_888
    assert work.train_token_flops(12, 768, 3072) == 6.0 * 12 * by_hand


def test_boosting_work_by_hand():
    one = work.hist_pass_work(1000, 28, 256)
    assert one["bytes"] == 1000 * 28 * 4 + 1000 * 8
    assert one["ops_min"] == 1000 * 28 * 3
    assert one["ops_onehot"] == 2 * 1000 * 28 * 256 * 3
    it = work.boost_iteration_work(1000, 28, 256, 31)
    assert it["levels"] == work.tree_levels(31) == 5
    assert it["bytes"] == 5 * one["bytes"] + 24 * 1000
    pk = peaks.peaks("TPU v5 lite")
    assert work.least_seconds(it, pk) == it["bytes"] / 819e9


def test_an_unknown_chip_is_an_error():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
