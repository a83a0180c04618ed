"""The FLOP functions against hand counts at the widths of the
benchmark's configurations."""
import pytest

from chipbench import core


def _cfg(name):
    return core.load_json(core.HERE / "configs" / f"{name}.json")


def _model():
    return core.load_module("costs", "model")


def test_model_params_match_published_sizes():
    model = _model()
    sm = _cfg("smollm-135m-train")
    # 30 x (576 x (9 + 6) x 64 + 9 x 64 x 576 + 3 x 576 x 1536)
    assert model.layer_matmul_params(sm) == 576 * 15 * 64 + 576 * 576 \
        + 3 * 576 * 1536
    n = 30 * model.layer_matmul_params(sm) + 49152 * 576
    assert abs(n - 134.5e6) < 0.2e6          # SmolLM-135M: 134.5 M


def test_attention_flops_hand_count():
    model = _model()
    sm = _cfg("smollm-135m-train")
    feat = 2 * 64 * 64 + 2 * 64 * 256
    hand = (9 + 3) * feat + 9 * (2 * 256 * 64 + 2 * 256) \
        + 3 * (2 * 256 * 64 + 256)
    assert model.attention_flops_per_token(sm) == hand


def test_train_flops_are_six_n_plus_attention():
    model = _model()
    sm = _cfg("smollm-135m-train")
    n = 30 * model.layer_matmul_params(sm) + 576 * 49152
    attn = 30 * model.attention_flops_per_token(sm)
    assert model.train_flops_per_token(sm) == 6 * n + 3 * attn


@pytest.mark.parametrize("steps,window_s", [(9, 4.0), (1, 0.5)])
def test_train_mfu_reader(steps, window_s):
    """The reader's share is the hand count: 6 N + 3 x attention FLOPs a
    token, over the window times the chip's bf16 peak."""
    sm = _cfg("smollm-135m-train")
    mod = core.load_module("metrics", "train_mfu")
    peaks = core.peaks("TPU v5 lite")
    ctx = {"train_steps": steps, "tokens_per_step": 8 * 2048,
           "config": sm, "window_s": window_s, "peaks": peaks,
           "costs": lambda name: core.load_module("costs", name)}
    per_tok = 6 * (30 * (576 * 15 * 64 + 576 * 576 + 3 * 576 * 1536)
                   + 576 * 49152) \
        + 3 * 30 * _model().attention_flops_per_token(sm)
    hand = 100 * steps * 8 * 2048 * per_tok / (window_s * 197e12)
    assert mod.read(ctx) == pytest.approx(hand, rel=1e-12)
    assert mod.read(dict(ctx, train_steps=0)) is None
