"""The reduction from a trace to busy time, the longest ops and
attributed idle gaps, on a hand-built trace with known answers."""
import pytest

from chipbench import core, trace
from chipbench.trace import Event


def _toy():
    # host spans: the window [0, 300); steps [0, 100), [110, 300)
    spans = [Event("window", 0, 300), Event("train_step", 0, 100),
             Event("train_step", 110, 300)]
    # ops, with overlap inside the first step and a loop that holds
    # others
    ops = [Event("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)", 10, 40),
           Event("%convolution.3 = bf16[8,8]{1,0} convolution(...)", 30,
                 60),
           Event("%while.2 = (s32[]) while((s32[]) %t)", 70, 280),
           Event("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %b)", 70, 95),
           Event("%convolution.3 = bf16[8,8]{1,0} convolution(...)", 150,
                 250),
           Event("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)", 250, 280)]
    return trace.Trace(ops, spans)


def test_busy_is_the_union_of_op_intervals():
    tr = _toy()
    assert tr.busy_intervals() == [[10, 60], [70, 280]]
    assert tr.busy_ns() == 50 + 210
    assert tr.busy_ns(0, 200) == 50 + 130
    assert tr.span_bounds() == (0, 300)
    no_window = trace.Trace(tr.ops, tr.spans[1:])
    assert no_window.span_bounds() == (0, 300)


def test_idle_gaps_are_named_by_host_span():
    gaps = _toy().idle_gaps(10)
    # gaps [0,10) and [60,70) in the first step, [280,300) in the second
    assert sorted(round(g[1] * 1e9) for g in gaps) == [10, 10, 20]
    assert gaps[0] == ["train_step", pytest.approx(20e-9)]
    between = trace.Trace([Event("f", 0, 100), Event("f", 112, 300)],
                          _toy().spans)
    assert between.idle_gaps(1) == [["none", pytest.approx(12e-9)]]


def test_top_ops_leave_out_loops():
    top = _toy().top_ops(10)
    assert top[0] == ["convolution.3 convolution", pytest.approx(130e-9)]
    assert ["fusion.1 fusion", pytest.approx(60e-9)] in top
    assert all("while" not in name for name, _ in top)


def test_idle_share_reader():
    tr = _toy()
    mod = core.load_module("metrics", "device_idle_share.train")
    ctx = {"trace": tr, "train_steps": 2, "span_ns": tr.span_bounds()}
    assert mod.read(ctx) == pytest.approx(100 * (1 - 260 / 300))
    assert mod.read({"trace": tr, "span_ns": (0, 300)}) is None


def test_reduction_of_a_recorded_trace():
    """A trace recorded on a v5e (the toy training cell, a short traced
    window): the reduction finds the window, the steps and the ops, and
    its busy time, gaps and shares add up."""
    tr = trace.load(core.HERE / "tests" / "data" / "trace")
    names = [e.name for e in tr.spans]
    assert names.count("window") == 1 and "train_step" in names
    assert tr.ops
    lo, hi = tr.span_bounds()
    assert lo < hi
    busy = tr.busy_ns(lo, hi)
    assert 0 < busy <= hi - lo
    # the union, swept independently
    edges = sorted((max(e.start, lo), min(e.end, hi)) for e in tr.ops
                   if min(e.end, hi) > max(e.start, lo))
    total, end = 0.0, lo
    for s, t in edges:
        total += max(0.0, t - max(s, end))
        end = max(end, t)
    assert busy == pytest.approx(total)
    gaps = tr.idle_gaps(10 ** 6)
    assert sum(g[1] for g in gaps) * 1e9 == pytest.approx(hi - lo - busy)
    assert {g[0] for g in gaps} <= {"train_step", "none"}
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    mod = core.load_module("metrics", "device_idle_share.train")
    share = mod.read({"trace": tr, "train_steps": 1, "span_ns": (lo, hi)})
    assert share == pytest.approx(100 * (1 - busy / (hi - lo)))
    top = tr.top_ops(10)
    assert top and all(" while" not in n for n, _ in top)
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
