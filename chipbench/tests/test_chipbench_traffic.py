"""The traffic generator repeats for a seed and gives every row of
every step its own tokens."""
import numpy as np

from chipbench import core, traffic


def _mix():
    return core.load_json(core.HERE / "traffic" / "finetune-8x2048.json")


def _steps(seed, n):
    feed = traffic.train_rows(_mix(), seed, 49152)
    return np.stack([next(feed) for _ in range(n)])


def test_train_rows_differ_and_repeat():
    t = _mix()
    a = _steps(2**40 + 5, 2)
    assert a.shape == (2, t["batch"], t["seq"] + 1)
    assert a.dtype == np.int32
    assert (a == _steps(2**40 + 5, 2)).all()
    rows = {r.tobytes() for r in a.reshape(-1, a.shape[-1])}
    assert len(rows) == 2 * t["batch"]
    assert (a != _steps(2**40 + 6, 2)).any()


def test_tokens_cover_the_vocabulary():
    a = _steps(-3, 4)
    assert a.min() >= 0 and a.max() < 49152
    counts = np.bincount(a.ravel(), minlength=49152)
    # uniform draws: 65,568 tokens over 49,152 ids
    assert (counts > 0).mean() > 0.7


def test_seed_words_take_any_whole_number():
    for seed in (0, -1, 2**31 + 7, 2**70 + 3):
        w = traffic.seed_words(seed, 4)
        assert w.dtype == np.uint32 and len(w) == 4
    assert (traffic.seed_words(5, 4) != traffic.seed_words(6, 4)).any()
