"""The benchmark's operation and byte counts against hand counts."""
import pytest

import _tiny  # noqa: F401
from bench import flops, spec

DEEPSEEK = spec.cell("deepseek-llm-7b.collm.chat")["config"]
YI = spec.cell("yi-6b.collm.summarize")["config"]


# embedding and output head 2 V d; per block q, k, v, o, three MLP matrices
# and two norms; then the final norm and one norm per exit
@pytest.mark.parametrize("cfg,params", [
    (DEEPSEEK, 2 * 102400 * 4096
     + 30 * (4096 * 128 * (32 + 64) + 32 * 128 * 4096 + 3 * 4096 * 11008
             + 2 * 4096) + 3 * 4096),
    (YI, 2 * 64000 * 4096
     + 32 * (4096 * 128 * (32 + 8) + 32 * 128 * 4096 + 3 * 4096 * 11008
             + 2 * 4096) + 3 * 4096)])
def test_params_from_shapes(cfg, params):
    exits = tuple(cfg["assumed"]["exit_layers"])
    assert flops.count_params(cfg, exits) == params


def test_served_bytes_match_the_issue():
    assert abs(2 * flops.count_params(DEEPSEEK, (8, 15)) - 13.82e9) < 0.01e9
    assert abs(2 * flops.count_params(YI, (8, 16)) - 12.12e9) < 0.01e9


def test_kv_bytes_per_token():
    # DeepSeek keeps 37 layer caches (edge 1-15, cloud 9-30) of 32 KV heads
    assert flops.kv_bytes_per_token(DEEPSEEK, 37) == 37 * 2 * 32 * 128 * 2
    assert flops.kv_bytes_per_token(YI, 40) == 40 * 2 * 4 * 128 * 2


def test_step_work_hand_count():
    cfg = _tiny.CONFIG     # d 64, 4 heads x 16, 2 KV heads, ff 128, V 256
    layer = 64 * 16 * (4 + 4) + 4 * 16 * 64 + 3 * 64 * 128      # 36864
    f, b = flops.step_work(cfg, 2, 2, [5, 7])
    per_row = lambda ctx: 2 * (2 * layer + 4 * 4 * 16 * ctx) + 2 * 2 * 256 * 64
    assert f == per_row(5) + per_row(7)
    weights = (2 * (layer + 2 * 64) + 256 * 64 + 2 * 64) * 2
    assert b == weights + 2 * 2 * 2 * 16 * 2 * 12
    assert flops.step_work(cfg, 2, 2, []) == (0.0, 0.0)


def test_prefill_hand_count():
    cfg = _tiny.CONFIG
    layer = 36864
    p = 10
    want = 3 * (2 * layer * p + 2 * 4 * 16 * p * (p + 1)) + 2 * 256 * 64
    assert flops.prefill_flops(cfg, 3, 1, p) == want
