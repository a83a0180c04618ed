"""``prf_kernel_share.train``: the PRF attention's device time in the
causal mix's Pallas kernels, on a hand-built trace with a known answer
and on a recording that has no such kernel."""
import shutil

import pytest

from chipbench import core, scopes, trace

DATA = core.HERE / "tests" / "data" / "trace"
US = 1_000_000           # picoseconds in a microsecond
PATH = "jit(train_step)/transpose(jvp())/while/body/checkpoint"


def _read(ctx):
    return core.load_module("metrics", "prf_kernel_share.train").read(ctx)


@pytest.mark.parametrize("tf_op, kernel", [
    (f"{PATH}/prf_mix/jvp(prf_mix_fwd)/pallas_call", True),
    (f"{PATH}/prf_mix/transpose(jvp(prf_mix_bwd_dq))/pallas_call", True),
    (f"{PATH}/prf_mix/transpose(jvp(prf_mix_bwd_dkv))/pallas_call:", True),
    (f"{PATH}/prf_mix/...cqm,...ckm->...cqk/dot_general:", False),
    (f"{PATH}/prf_features/exp:", False),
    (None, False),
])
def test_kernel_ops_are_known_by_their_path(tf_op, kernel):
    metric = core.load_module("metrics", "prf_kernel_share.train")
    assert metric.is_kernel(tf_op) is kernel


def test_share_of_the_layer_in_the_kernels(tmp_path):
    """Two executions of the step: in each, 300 us of kernels and 100 us
    of XLA ops under ``prf_mix``, 100 us under ``prf_features``, and an
    MLP op that counts in neither."""
    space = scopes.xspace_class()()
    plane = space.planes.add(name=scopes.DEVICE)
    plane.stat_metadata[1].name = "tf_op"
    ops = [("prf_mix_fwd.1", f"{PATH}/prf_mix/jvp(prf_mix_fwd)/"
            "pallas_call", 0, 100),
           ("prf_mix_bwd_dkv.1", f"{PATH}/prf_mix/transpose(jvp("
            "prf_mix_bwd_dkv))/pallas_call", 100, 300),
           ("copy.1", f"{PATH}/prf_mix/copy:", 300, 400),
           ("fusion.2", f"{PATH}/prf_features/exp:", 400, 500),
           ("fusion.3", f"{PATH}/mlp/dot_general:", 500, 900)]
    for k, (name, op, _, _) in enumerate(ops, start=2):
        m = plane.event_metadata[k]
        m.name = name
        m.stats.add(metadata_id=1, str_value=op)
    plane.event_metadata[1].name = "jit_train_step(1)"
    mods = plane.lines.add(name="XLA Modules", timestamp_ns=0)
    line = plane.lines.add(name="XLA Ops", timestamp_ns=0)
    for start in (1000, 2000):
        mods.events.add(metadata_id=1, offset_ps=start * US,
                        duration_ps=900 * US)
        for k, (_, _, s, t) in enumerate(ops, start=2):
            line.events.add(metadata_id=k, offset_ps=(start + s) * US,
                            duration_ps=(t - s) * US)
    mods.events.add(metadata_id=1, offset_ps=5000 * US, duration_ps=1)
    (tmp_path / "h.xplane.pb").write_bytes(space.SerializeToString())
    ctx = {"trace_dir": tmp_path, "span_ns": (500e3, 4000e3)}
    assert _read(ctx) == pytest.approx(100 * 300 / 500)


def test_a_recording_without_the_kernels_reads_nothing(tmp_path):
    """The scoped recording of the toy cell runs the XLA mix only."""
    name = "tiny-finetune-scoped.xplane.pb"
    shutil.copy(DATA / name, tmp_path / name)
    ctx = {"trace_dir": tmp_path,
           "span_ns": trace.load(tmp_path).span_bounds()}
    assert _read(ctx) is None
