"""What the dense read of a latent model has to move and compute
(`reduce/latent_dense_attention_cost.py`), against hand counts at Kimi-K2.5's
published widths, and the metric files that read the new scope."""

import pytest

from modelcfg import load_json
from reduce import costs, grouped_matmul_cost, latent_dense_attention_cost


def test_the_kimi_decode_read_counts_every_cached_latent_once_a_layer():
    sizes = load_json("layer_metrics", "latent_dense_decode_attn_roofline")["roofline"]["sizes"]
    assert sizes == {"n_heads": 64, "latent_width": 576, "value_width": 512, "layers": 7}
    # a chunk of 8 steps over 10 rows of 13,000 tokens: every one read, every step
    read = latent_dense_attention_cost.latent_decode_attention(
        kv_tokens_read=10 * 8 * 13000, active_rows=10, steps=8, calls=999, **sizes)
    latents = 10 * 8 * 13000 * 1152  # a token's 576-wide latent in bf16, once for key and value
    q_and_out = 8 * 10 * 64 * (576 + 512) * 2
    assert read["bytes"] == 7 * (latents + q_and_out)
    assert read["ops"] == 7 * 2 * 10 * 8 * 13000 * 64 * (576 + 512)
    assert 119 < read["ops"] / read["bytes"] < 121  # 121 operations a byte of latent
    # whatever implements it: the kernel's call count moves nothing
    again = latent_dense_attention_cost.latent_decode_attention(
        kv_tokens_read=10 * 8 * 13000, active_rows=10, steps=8, calls=1, **sizes)
    assert again == read
    # the larger bound: on a v5e the bytes (121 operations a byte is under its ridge of 240)
    least, bound = costs.roofline_seconds(read, load_json("reduce", "peaks")["devices"]["TPU v5 lite"])
    assert bound == "memory" and least == pytest.approx(read["bytes"] / 819e9)


def test_the_kimi_segment_counts_causal_pairs_at_the_two_published_widths():
    sizes = load_json("layer_metrics", "latent_dense_segment_attn_roofline")["roofline"]["sizes"]
    assert sizes == {"n_heads": 64, "qk_head_dim": 192, "v_head_dim": 128, "latent_width": 576,
                     "layers": 7}
    # a 2,048-token segment at offset 8,192: query i sees 8,192 + i + 1 keys
    pairs = sum(8192 + i + 1 for i in range(2048))
    walk = latent_dense_attention_cost.latent_segment_attention(
        kv_tokens_read=pairs, real_tokens=2048, offset=8192, steps=1, calls=7, **sizes)
    assert walk["ops"] == 7 * 2 * pairs * 64 * (192 + 128)  # q.k over 192, p.v over 128
    assert walk["bytes"] == 7 * 2 * (2048 * 64 * (192 + 128) + 576 * (8192 + 2048))
    # 0.63 of the products of a model whose key and value are both 256 wide
    assert walk["ops"] / (7 * 4 * pairs * 64 * 256) == pytest.approx(0.625)
    # a last segment of 100 real queries counts its real pairs alone
    tail = latent_dense_attention_cost.latent_segment_attention(
        kv_tokens_read=sum(16384 - 100 + i + 1 for i in range(100)), real_tokens=100,
        offset=16284, steps=1, calls=7, **sizes)
    assert tail["ops"] < walk["ops"] / 10


def test_the_kimi_metric_files_read_the_scopes_and_attributes_the_program_has():
    from langstream_tpu.models.transformer import SCOPES

    step = load_json("layer_metrics", "latent_read_ms_per_step")
    assert (step["reader"], step["program"], step["span"], step["per"], step["scopes"]) == (
        "trace_scope", "_paged_decode_chunk", "engine.decode_chunk", "steps",
        ["attention.latent.read"])
    segment = load_json("layer_metrics", "latent_read_ms_per_1k_segment_tokens")
    assert (segment["program"], segment["per"], segment["scopes"]) == (
        "_paged_segment_and_sample", "computed_tokens", ["attention.latent.read"])
    decode = load_json("layer_metrics", "latent_dense_decode_attn_roofline")
    assert decode["scopes"] == ["attention.latent.read"]
    assert decode["roofline"]["span_attrs"] == ["kv_tokens_read", "active_rows"]
    walk = load_json("layer_metrics", "latent_dense_segment_attn_roofline")
    assert walk["scopes"] == ["flash_segment_attention"]
    assert walk["roofline"]["span_attrs"] == ["kv_tokens_read", "real_tokens", "offset"]
    for definition in (step, segment, decode):
        assert set(definition["scopes"]) <= set(SCOPES)
    experts = load_json("layer_metrics", "moe7168x2048_grouped_matmul_roofline")
    assert experts["roofline"]["sizes"] == {"d_model": 7168, "d_ff": 2048}
    assert experts["roofline"]["cost"] == "grouped_matmul_cost.grouped_matmul"
    # a decode step of 16 rows x top-8 of 384 that touches 3 of the 12 held experts of 6 layers
    work = grouped_matmul_cost.grouped_matmul(
        moe_local=6 * 4, moe_touched=6 * 3, steps=1, calls=12, **experts["roofline"]["sizes"])
    assert work["bytes"] == 6 * 3 * 3 * 7168 * 2048 + 6 * 4 * 3 * (7168 + 2048) * 2
