"""The yardstick's arithmetic against counts made by hand."""
import json
import math
import os

import pytest

from bench import counts, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def model(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["model"]


# qwen3-1.7b at 4 layers: d 2048, 16 q / 8 kv heads of 128, d_ff 6144,
# vocab 151936, tied.  Per layer: wq, wo 2048*2048; wk, wv 2048*1024;
# q_norm, k_norm 128; ln1, ln2 2048; mlp 3 * 2048 * 6144.
QWEN_LAYER = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 2 * 128 + 2 * 2048 \
    + 3 * 2048 * 6144
QWEN = 2048 + 151936 * 2048 + 4 * QWEN_LAYER
# mistral-7b at 1 layer: d 4096, 32 q / 8 kv heads of 128, d_ff 14336,
# vocab 32000, untied (tok and unembed).
MISTRAL = 4096 + 2 * 32000 * 4096 + (2 * 4096 * 4096 + 2 * 4096 * 1024
                                     + 2 * 4096 + 3 * 4096 * 14336)


def test_param_counts():
    assert QWEN == 512_510_976
    assert MISTRAL == 480_260_096
    assert counts.n_params(model("qwen3-1.7b")) == QWEN
    assert counts.n_params(model("mistral-7b")) == MISTRAL


def test_leaf_order_is_sorted_flatten_order():
    paths = [p for p, _ in counts.leaf_shapes(model("qwen3-1.7b"))]
    assert paths == sorted(paths)
    assert paths[:2] == ["embed/final_norm", "embed/tok"]
    assert "embed/unembed" in [p for p, _ in
                               counts.leaf_shapes(model("mistral-7b"))]


def test_flops_per_token():
    # 6 per weight multiply-add (layers + head) plus causal attention:
    # 12 * H * Dh per (query, visible key), (S + 1) / 2 keys on average
    qwen_matmul = 4 * (2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144) \
        + 151936 * 2048
    assert counts.flops_per_token(model("qwen3-1.7b"), 4096) == \
        6 * qwen_matmul + 12 * 4 * 16 * 128 * 4097 / 2
    mis_matmul = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 \
        + 32000 * 4096
    assert counts.flops_per_token(model("mistral-7b"), 4096) == \
        6 * mis_matmul + 12 * 32 * 128 * 4097 / 2


def test_qwen_bucket_plan_and_topk_bytes():
    m = model("qwen3-1.7b")
    plan = counts.bucket_plan([math.prod(sh)
                               for _, sh in counts.leaf_shapes(m)], 0.01)
    # final_norm | tok (75 rows) | k_norm+q_norm | wk | wo | wq | wv |
    # ln1+ln2 | w_down | w_gate | w_up
    assert [b["rows"] for b in plan] == [1, 75, 1, 2, 4, 4, 2, 1, 12, 12, 12]
    kept = [21, 75 * 41489, 6 + 6, 2 * 41944, 4 * 41944, 4 * 41944,
            2 * 41944, 82 + 82, 12 * 41944, 12 * 41944, 12 * 41944]
    assert [b["rows"] * b["k"] for b in plan] == kept
    assert counts.kept_per_node(m, 0.01) == sum(kept) == 5_125_184
    assert counts.topk_bytes(m, 0.01) == 4 * QWEN + 8 * 5_125_184
    assert counts.ef_update_bytes(m) == 8 * 4 * QWEN


def test_mistral_topk_budget():
    m = model("mistral-7b")
    # leaves of exactly 4 Mi elements (wk, wv) are one top-k row each
    plan = counts.bucket_plan([4096, 32000 * 4096, 32000 * 4096,
                               4096 * 1024, 4096 * 4096, 4096 * 4096,
                               4096 * 1024, 4096, 4096,
                               14336 * 4096, 4096 * 14336, 4096 * 14336],
                              0.01)
    assert [b["rows"] for b in plan] == [1, 32, 32, 1, 4, 4, 1, 1, 14, 14, 14]
    assert counts.kept_per_node(m, 0.01) == sum(b["rows"] * b["k"]
                                                for b in plan)


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
