"""The FLOP and byte functions against hand counts at the cells' shapes."""

import pytest

from chipbench_fixtures import CHIP  # noqa: F401  (sets the path)

import costs  # noqa: E402
import harness  # noqa: E402

CAT = harness.Catalog()
Q05 = costs.shape(CAT.config("qwen1_5_0_5b"))
Q14 = costs.shape(CAT.config("qwen3_14b_8l"))


def _metric(name):
    return CAT.metric(name)


def test_block_params():
    # 4 x 1024^2 + 3 x 1024 x 2816
    assert costs.block_params(Q05) == 12_845_056
    # 2 x 5120^2 + 2 x 5120 x 1024 + 3 x 5120 x 17408
    assert costs.block_params(Q14) == 330_301_440


def test_token_flops():
    # 24 x (12845056 + 4 x 1000 x 16 x 64) + 2 x 1024 x 151936
    assert costs.token_flops(Q05, 1000, True) == 717_750_272
    assert costs.token_flops(Q05, 1000, False) == 406_585_344


def test_prompt_flops():
    # 24 x (12845056 x 3 + 4 x 16 x 64 x 3 x 4 / 2) + head once
    want = 24 * (12_845_056 * 3 + 4 * 16 * 64 * 6) + 2 * 1024 * 151936
    assert costs.prompt_flops(Q05, 3) == want


def test_nm_spmm_decode_call():
    m = _metric("nm_spmm_decode_roofline.chat")
    f, b = m.call_cost(32, 1024, 1024)
    assert f == 2 * 32 * 1024 * 1024
    # values bf16 + positions int8 (K/2 x N), x bf16, y f32, bias f32
    assert b == 512 * 1024 * 3 + 32 * 1024 * 2 + 32 * 1024 * 4 + 1024 * 4
    # off-tile shapes pad to 128 and M to a multiple of 8
    f, _ = m.call_cost(5, 1000, 1000)
    assert f == 2 * 8 * 1024 * 1024


def test_paged_attn_token():
    m = _metric("paged_attn_roofline.chat")
    f, b = m.token_cost(Q05, 1000, 16, 2)
    assert f == 4 * 1000 * 16 * 64
    assert b == 2 * 63 * 16 * 16 * 64 * 2          # 63 whole pages
    f, b = m.token_cost(Q14, 16, 16, 2)
    assert (f, b) == (4 * 16 * 40 * 128, 2 * 16 * 8 * 128 * 2)


def test_prune_block_flops_hand_count():
    s = {"d": 4, "f": 8, "h": 2, "kv": 2, "hd": 2, "layers": 1, "vocab": 8}
    # two forwards of 2 x 2 x 160 + 2 x 1 x 2 x 2 x 2 x 2 = 672 each;
    # Hessians 2 x 2 x (16 + 16 + 16 + 64) = 448; solves: the inverses
    # 4 x 64 + 2 x 64 + 512 and the row updates 4 x 4 x 26 2/3 (wq, wk,
    # wv, wo) + 2 x 8 x 26 2/3 (wi, wg) + 4 x (42 2/3 + 117 1/3) (mlp.wo)
    solves = (6 * 64 + 512) + 16 * (80 / 3) + 16 * (80 / 3) + 4 * 160
    assert solves == pytest.approx(2389 + 1 / 3)
    assert costs.prune_block_flops(s, 1, 2, 4) == pytest.approx(
        2 * 672 + 448 + solves)


def test_window_flops_counts_prefill_once():
    run = {"config": CAT.config("qwen1_5_0_5b"), "window": (0.0, 10.0),
           "requests": [{"prompt_len": 100, "events": [
               (1.0, 1), (2.0, 8), (11.0, 8)]}]}
    want = costs.prompt_flops(Q05, 100) + sum(
        costs.token_flops(Q05, 100 + j, True) for j in range(1, 9))
    assert costs.window_flops(run) == pytest.approx(want)
